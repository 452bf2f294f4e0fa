"""Sweep the decode-attention kernel's block of positions on the chip.

    chiprun -- python scripts/decode_block_sweep.py
    JAX_PLATFORMS=cpu python scripts/decode_block_sweep.py --describe

Each case is one serving cell's pool at its own shape with lanes at the
depths its traced runs show (PERF.md section 5): the kernel
(`ops/decode_attention.py`, `attend`) called once a reading layer, as a
decode step calls it, over every block that divides the pool's depth,
beside the chunk loop it replaces (`inference/decode.py`,
`_chunked_cached_attention`) on the same pools. `--describe` compiles
every candidate for a v5e that is described, not attached: what Mosaic
refuses it refuses there, at no chip time, and nothing it prints is a
time. What `decode_block` answers came from here (PERF.md section 6,
PR 35). Lines go to standard output and to
chiprun_out/decode_block_sweep.jsonl.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

OUT = os.path.join(ROOT, "chiprun_out", "decode_block_sweep.jsonl")

# slots, depth, heads, kv heads, head size, reading layers, lanes that
# decode and how their depths lie (PERF.md section 5); `reads`: how many
# layers read ONE layer of the pool
CASES = {
    "chat": dict(B=40, S=1280, H=32, KV=8, Hd=128, layers=16,
                 depths=[1100, 200, 120, 80, 43]),
    "batch": dict(B=64, S=1280, H=32, KV=8, Hd=128, layers=4,
                  live=47, lo=100, hi=1250),
    "reason": dict(B=128, S=2560, H=20, KV=1, Hd=128, layers=2,
                   live=69, lo=64, hi=2500, mean=700),
    "offline_global": dict(B=64, S=4096, H=40, KV=20, Hd=64, Dv=128,
                           layers=1, reads=8, live=64, lo=200, hi=3800,
                           mean=1384, out="float32"),
    "offline_ring": dict(B=64, S=640, H=40, KV=20, Hd=64, Dv=128, layers=8,
                         live=64, lo=200, hi=3800, mean=1384,
                         window=512, ring=True, out="float32"),
}


def emit(**line):
    print(json.dumps(line), flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(line) + "\n")


def positions(case, seed):
    """(pos [B], valid [B]) as numpy: the case's lanes at its depths."""
    import numpy as np

    rng = np.random.RandomState(seed)
    B = case["B"]
    if "depths" in case:
        depth = np.asarray(case["depths"])
    else:
        # a skewed draw between lo and hi with about the case's mean
        u = rng.rand(case["live"])
        mean = case.get("mean", (case["lo"] + case["hi"]) / 2)
        power = (case["hi"] - mean) / max(mean - case["lo"], 1)
        depth = (case["lo"] + (case["hi"] - case["lo"])
                 * u ** power).astype(int)
        depth[0] = case["hi"]
    pos, valid = np.zeros(B, np.int32), np.zeros(B, bool)
    lanes = rng.permutation(B)[:len(depth)]
    pos[lanes], valid[lanes] = depth - 1, True
    return pos, valid


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smallest", type=int, default=64)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import peaks
    from metaflow_tpu import device
    from metaflow_tpu.inference import decode
    from metaflow_tpu.ops import decode_attention as da

    one = None
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
    elif not device.on_tpu():
        raise SystemExit("a time comes from the chip only")
    else:
        emit(what="device", **device.describe())
        hbm_bytes_per_s = peaks.peak(
            jax.devices()[0].device_kind)["hbm_bytes_per_s"]

    for name in args.cases.split(","):
        case = CASES[name]
        B, S, H, KV, Hd = (case[key] for key in ("B", "S", "H", "KV", "Hd"))
        Dv, L = case.get("Dv", Hd), case["layers"]
        reads = case.get("reads", L)
        kw = dict(v_head_dim=Dv, window=case.get("window"),
                  ring=case.get("ring", False), dtype=case.get("out"))
        pos, valid = positions(case, args.seed)
        depth = np.where(valid, pos + 1, 0)
        seen = np.minimum(depth, case.get("window") or S)
        bytes_a_position = 2 * KV * Hd * 2
        shapes = dict(q=(B, 1, H, Hd), k=(L, B, S, KV * Hd))
        if args.describe:
            sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                         sharding=one)
            q, ck = (sds(shapes[a], jnp.bfloat16) for a in "qk")
            cv = ck
            d_pos, d_valid = sds((B,), jnp.int32), sds((B,), jnp.bool_)
        else:
            keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
            q = jax.random.normal(keys[0], shapes["q"], jnp.bfloat16)
            ck = jax.random.normal(keys[1], shapes["k"], jnp.bfloat16)
            cv = jax.random.normal(keys[2], shapes["k"], jnp.bfloat16)
            d_pos, d_valid = jnp.asarray(pos), jnp.asarray(valid)

        def step(attention):
            """`reads` calls, as a decode step's layers make them."""
            def run(q, ck, cv, pos, valid):
                lanes = da.live_lanes(valid) + (valid,)

                def layer(i, total):
                    out = attention(q, ck, cv, pos, i % L, lanes)
                    return total + out.astype(jnp.float32)

                first = jax.eval_shape(
                    lambda: attention(q, ck, cv, pos, 0, lanes))
                return jax.lax.fori_loop(
                    0, reads, layer, jnp.zeros(first.shape, jnp.float32))
            return jax.jit(run)

        candidates = [("loop", None)] + [
            ("kernel", b) for b in range(16, S + 1, 16)
            if S % b == 0 and b >= args.smallest
            and b * KV * Hd * 2 <= 4 * 2 ** 20]
        want = None
        for impl, block in candidates:
            if impl == "loop":
                fn = step(lambda q, ck, cv, pos, layer, lanes:
                          decode._chunked_cached_attention(
                              q, ck, cv, pos, layer, **kw))
            else:
                fn = step(lambda q, ck, cv, pos, layer, lanes, block=block:
                          da.attend(q, ck, cv, pos, layer, *lanes,
                                    block=block, **kw))
            line = dict(what="describe" if args.describe else "time",
                        case=name, impl=impl, block=block, lanes=int(
                            valid.sum()), mean_depth=round(float(
                                depth[valid].mean()), 1))
            if block:
                fetched = int(np.asarray(da.fetched_positions(
                    np.minimum(depth, S), block, S)).sum())
                line.update(
                    answer=block == da.decode_block(S, KV * Hd, jnp.bfloat16),
                    grid_steps=B * (S // block) * reads,
                    needed_over_fetched=round(
                        float(seen.sum()) / max(fetched, 1), 3))
            t0 = time.perf_counter()
            try:
                operands = (q, ck, cv, d_pos, d_valid)
                if args.describe:
                    compiled = fn.lower(*operands).compile()
                    line["temporaries"] = \
                        compiled.memory_analysis().temp_size_in_bytes
                else:
                    got = jax.block_until_ready(fn(*operands))
                line["compile_s"] = round(time.perf_counter() - t0, 2)
                if not args.describe:
                    live = np.asarray(got, np.float32)[valid]
                    if want is None:
                        want = live
                    line["max_diff_from_loop"] = float(
                        np.abs(live - want).max())
                    t0 = time.perf_counter()
                    for _i in range(args.calls):
                        got = fn(*operands)
                    jax.block_until_ready(got)
                    ms = (time.perf_counter() - t0) * 1e3 / args.calls
                    need = float(seen.sum()) * bytes_a_position * reads
                    line.update(ms=round(ms, 3), roofline_pct=round(
                        100 * need / hbm_bytes_per_s / (ms / 1e3), 1))
            except Exception as ex:  # what the compiler refuses is a result
                line["refused"] = str(ex).strip().splitlines()[-1][:300]
            emit(**line)


if __name__ == "__main__":
    main()
