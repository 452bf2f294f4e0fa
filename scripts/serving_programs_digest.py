"""sha256 of the lowered text (`jit(...).lower(...).as_text()`, shapes
alone, nothing compiled or run) of every serving cell's programs at the
benchmark's sizes, in the tree at argv[1]: the decode step, the merged
shapes where the engine merges, the prefill shapes. How a change to
code the families share (inference/decode.py, serving/engine.py) is
shown to leave the other families' programs as they were:

    export JAX_PLATFORMS=cpu
    python scripts/serving_programs_digest.py <parent's tree> > a.json
    python scripts/serving_programs_digest.py . > b.json
    diff a.json b.json

A cell whose family the tree does not have is left out (so a cell a PR
adds is in its own side alone). Takes about two minutes a tree.
"""

import hashlib
import json
import os
import sys


def main():
    root = os.path.abspath(sys.argv[1])
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp

    from benchmark import configs, weights
    from metaflow_tpu.serving import SlotEngine

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    out = {}
    with open("BENCHMARK.json") as f:
        cells = json.load(f)["workloads"]
    for cell in cells:
        _, _, config, traffic = configs.load_cell(cell["name"])
        if not traffic["kind"].startswith("serve"):
            continue
        s = config["serving"]
        try:
            dims = configs.dims(config)
            _, cfg = configs.program_config(config, s["max_seq_len"])
        except (ImportError, KeyError):   # a family this tree lacks
            continue
        params = jax.eval_shape(
            lambda: weights.init_params(jax.random.PRNGKey(0), dims))
        eng = SlotEngine(params, cfg, max_slots=s["slots"],
                         max_seq_len=s["max_seq_len"],
                         prefill_chunk=s["prefill_chunk"])
        cache = jax.eval_shape(lambda: eng._cache)
        B = s["slots"]
        mask = jax.ShapeDtypeStruct((B,), jnp.bool_)
        texts = {"decode": eng._decode_greedy_fn.lower(
            params, cache, i32(B), i32(B), mask).as_text()}
        for R, W in eng.prefill_shapes(2 * s["prefill_chunk"]):
            if eng.merges:
                rows = {"tokens": i32(R, W), "slots": i32(R),
                        "start": i32(R), "n_real": i32(R)}
                if hasattr(eng, "launch_decode"):   # since PR 45: the
                    # program leaves the token of a row that ends at its lane
                    rows["ends"] = jax.ShapeDtypeStruct((R,), jnp.bool_)
                texts["merged_%dx%d" % (R, W)] = \
                    eng._decode_greedy_fn.lower(
                        params, cache, i32(B), i32(B), mask,
                        rows=rows).as_text()
            n_real = i32(R) if eng.recurrent or eng._tail else None
            texts["prefill_%dx%d" % (R, W)] = eng._prefill_fn.lower(
                params, cache, i32(R, W), i32(R), i32(R), n_real).as_text()
        out[cell["name"]] = {
            k: (hashlib.sha256(v.encode()).hexdigest()[:16], len(v))
            for k, v in texts.items()}
        out[cell["name"]]["attn_impl"] = eng.attn_impl
    print(json.dumps(out, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
