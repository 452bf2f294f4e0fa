#!/usr/bin/env python3
"""Does the system still start on the chip? One run of the main path.

    python chip_smoke.py              one chip, Llama-3-8B widths
    python chip_smoke.py --chips 4    the sharded path only, four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --size tiny
                                      CPU rehearsal: every phase runs,
                                      then the platform check fails

With no arguments, four phases, each a child process, one at a time,
because a chip belongs to one process and this one stays off JAX:

  train    `python tests/flows/chip_smoke_flow.py run`: make_trainer on
           a one-device mesh, a few steps with a finite falling loss,
           the flash kernel in the step program, a checkpoint saved;
           then generate() on that checkpoint writes the reference
  serve    `python -m metaflow_tpu serve ChipSmokeFlow --step-name
           train`, slot engine: /healthz, mixed-length /v1/generate
           requests (some at once), greedy tokens against the reference,
           SIGTERM, a clean drain
  paged    the same with --paged
  kernels  flash attention, grouped matmul and the ring's flash block
           against plain references, compiled, at real widths

Any phase failing, or a platform that is not 'tpu', makes the exit code
non-zero. The last stdout line is the one JSON object the driver reads;
everything else a run has to say is on the lines before it.
"""

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
FLOW = os.path.join(HERE, "tests", "flows", "chip_smoke_flow.py")
MARK = "CHIP_SMOKE "
# f32 logits out of bf16 matmuls: where the server's sums run in another
# order than generate()'s, a runner-up within this of the winner may win
NEAR_TIE_LOGIT_TOL = 0.0625
# kernels against references: max |a - b| over max |b|, bf16 operands
KERNEL_REL_TOL = 2e-2
# four-chip losses against one-chip losses, same seed and batches
SHARDED_LOSS_REL_TOL = 2e-2


def log(msg):
    print("chip_smoke: " + msg, flush=True)


class PhaseFailed(Exception):
    pass


# ---------------------------------------------------------------------------
# parent side: children, one at a time
# ---------------------------------------------------------------------------


class Child(object):
    """A child process whose output is echoed as it comes, with the
    CHIP_SMOKE facts it printed kept by phase name."""

    def __init__(self, argv, env):
        self.facts = {}
        self.lines = []
        self.proc = subprocess.Popen(
            argv, env=env, cwd=HERE, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, errors="replace")
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.lines.append(line)
            at = line.find(MARK)
            if at >= 0:
                try:
                    fact = json.loads(line[at + len(MARK):])
                    self.facts[fact.get("phase")] = fact
                except ValueError:
                    pass
            # request token lists run to kilobytes; the facts are kept
            print("  | " + (line if len(line) <= 600
                            else line[:600] + " ..."), flush=True)

    def wait(self, timeout):
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.stop()
            raise PhaseFailed("no end after %ds" % timeout)
        self._reader.join(timeout=10)
        return rc

    def stop(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def run_to_end(argv, env, timeout):
    """(exit code, facts) of a child that runs to its end."""
    child = Child(argv, env)
    try:
        return child.wait(timeout), child.facts
    finally:
        child.stop()


def phase_train(args, env):
    argv = [sys.executable, FLOW, "run", "--size", args.size,
            "--seed", str(args.seed), "--serve-seq", str(args.serve_seq)]
    if args.layers is not None:
        argv += ["--layers", str(args.layers)]
    if args.size == "tiny":
        argv += ["--seq", "128"]
    rc, facts = run_to_end(argv, env, args.phase_timeout)
    seen = (facts.get("device") or {}).get("device")
    if rc != 0 and args.size != "tiny" and seen \
            and seen["platform"] != "tpu":
        # nothing to rehearse at full width without the chip: no result
        sys.exit("chip_smoke: JAX found no accelerator (%s)" % (seen,))
    if rc != 0:
        raise PhaseFailed("the flow run exited with code %d" % rc)
    train, ref = facts["train"], facts["reference"]
    budget = facts["train_budget"]
    log("train: %d layers, %.3f B params, budget %.2f GiB (state %.2f + "
        "transient %.2f + activations %.2f), peak_bytes_in_use %s"
        % (budget["layers"], budget["params"] / 1e9, budget["total_gib"],
           budget["state_gib"], budget["transient_gib"],
           budget["activations_gib"], train["peak_bytes_in_use"]))
    log("train: memory_stats %s" % json.dumps(train["memory_stats"]))
    log("train: losses %s" % " ".join("%.4f" % x for x in train["losses"]))
    log("train: init %.1fs, compile %.1fs, first step %.1fs, steps %s, "
        "checkpoint save %.1fs; reference: load %.1fs, generate %.1fs, "
        "peak_bytes_in_use %s"
        % (train["init_s"], train["compile_s"], train["first_step_s"],
           " ".join("%.3f" % x for x in train["step_s"]), train["save_s"],
           ref["load_s"], ref["generate_s"], ref["peak_bytes_in_use"]))
    row = facts["chip_row"]
    log("chip row: %r -> %s TFLOP/s bf16, %s GB/s"
        % (row["kind"], row["peak_tflops"], row["hbm_gbps"]))
    return {"device": train["device"], "reference": ref["requests"]}


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url, body=None, timeout=600):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def check_answer(ref, got):
    """Greedy tokens equal the reference's; a difference is admitted
    only as a near tie at its first position. Returns a short verdict."""
    want, n = ref["new_tokens"], ref["max_new_tokens"]
    new = got.get("new_tokens")
    if got.get("reason") != "length" or not isinstance(new, list) \
            or len(new) != n or got.get("tokens") != ref["prompt"] + new:
        raise PhaseFailed(
            "prompt of %d: want %d new tokens and reason 'length', got "
            "%r" % (len(ref["prompt"]), n,
                    {k: got.get(k) for k in ("reason", "new_tokens")}))
    if new == want:
        return "equal"
    i = next(j for j in range(n) if new[j] != want[j])
    ids, vals = ref["top_ids"][i], ref["top_logits"][i]
    gap = vals[0] - vals[ids.index(new[i])] if new[i] in ids else None
    if gap is None or gap > NEAR_TIE_LOGIT_TOL:
        raise PhaseFailed(
            "prompt of %d: token %d is %d, generate() said %d (its "
            "logit gap to that token: %s, tolerance %s)"
            % (len(ref["prompt"]), i, new[i], want[i], gap,
               NEAR_TIE_LOGIT_TOL))
    return "near tie at token %d (gap %.4f)" % (i, gap)


def phase_serve(args, env, reference, paged):
    name = "paged" if paged else "serve"
    port = free_port()
    argv = [sys.executable, "-m", "metaflow_tpu", "serve", "ChipSmokeFlow",
            "--step-name", "train", "--port", str(port), "--slots", "4",
            "--max-seq-len", str(args.serve_seq), "--prefill-chunk", "64"]
    if paged:
        argv.append("--paged")
    base = "http://127.0.0.1:%d" % port
    t0 = time.monotonic()
    child = Child(argv, env)
    try:
        while True:
            if child.proc.poll() is not None:
                raise PhaseFailed("the server exited with code %d before "
                                  "it was ready" % child.proc.returncode)
            if time.monotonic() - t0 > args.phase_timeout:
                raise PhaseFailed("the server was not ready in time")
            try:
                if http_json(base + "/healthz", timeout=5).get("ok"):
                    break
            except (OSError, ValueError, urllib.error.URLError):
                time.sleep(0.5)
        ready_s = time.monotonic() - t0

        def ask(ref):
            t = time.monotonic()
            got = http_json(base + "/v1/generate",
                            {"tokens": ref["prompt"],
                             "max_new_tokens": ref["max_new_tokens"]},
                            timeout=args.phase_timeout)
            return check_answer(ref, got), time.monotonic() - t

        def ask_all(refs):
            out, errs = [None] * len(refs), []

            def one(i):
                try:
                    out[i] = ask(refs[i])
                except Exception as ex:  # re-raised below, in the caller
                    errs.append(ex)

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(len(refs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errs:
                raise errs[0]
            return out

        # alone first (its time is mostly compilation), then the others
        # at once, then all of them at once: five requests on four slots
        first, first_s = ask(reference[0])
        t = time.monotonic()
        wave1 = ask_all(reference[1:])
        wave1_s = time.monotonic() - t
        t = time.monotonic()
        wave2 = ask_all(reference)
        wave2_s = time.monotonic() - t
        verdicts = [first] + [v for v, _ in wave1 + wave2]
        stats = http_json(base + "/v1/stats", timeout=30)

        child.proc.send_signal(signal.SIGTERM)
        rc = child.wait(120)
        if rc != 0 or not any("drained" in ln for ln in child.lines):
            raise PhaseFailed("no clean drain after SIGTERM (exit code "
                              "%s)" % rc)
    finally:
        child.stop()
    dev = next((json.loads(ln.split("device:", 1)[1])
                for ln in child.lines if "device:" in ln), None)
    if dev is None:
        raise PhaseFailed("the server did not say what it runs on")
    tail = next((ln.strip() for ln in child.lines
                 if ln.strip().startswith("compiles:")), "")
    log("%s: ready %.1fs, first request %.1fs, 4 at once %.1fs, 5 at once "
        "%.1fs (warm); %d answers, %d equal to generate(), %s"
        % (name, ready_s, first_s, wave1_s, wave2_s, len(verdicts),
           verdicts.count("equal"),
           "; ".join(v for v in verdicts if v != "equal") or "no near tie"))
    log("%s: %s" % (name, tail))
    log("%s: stats %s" % (name, json.dumps(
        {k: stats.get(k) for k in ("served", "decode_steps",
                                   "peak_in_flight", "kv_pages")})[:500]))
    if stats.get("served") != len(verdicts):
        raise PhaseFailed("the server counts %s served requests, %d were "
                          "answered" % (stats.get("served"), len(verdicts)))
    return {"device": dev}


def phase_child(args, env, phase):
    """kernels / multichip: this file again, as a child that may use JAX."""
    rc, facts = run_to_end(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         "--size", args.size, "--seed", str(args.seed)]
        + (["--layers", str(args.layers)] if args.layers is not None
           else []),
        env, args.phase_timeout)
    if rc != 0:
        raise PhaseFailed("%s exited with code %d" % (phase, rc))
    fact = facts[phase]
    log("%s: compiles: %s  peak_bytes_in_use: %s"
        % (phase, json.dumps(fact["compiles"]), fact["peak_bytes_in_use"]))
    return {"device": fact["device"]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", default="8b", choices=("8b", "tiny"),
                    help="model widths: Llama-3-8B (default) or "
                         "LlamaConfig.tiny() for the CPU rehearsal")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=None,
                    help="depth at --size 8b (default: the flow's)")
    ap.add_argument("--serve-seq", type=int, default=1024)
    ap.add_argument("--phase-timeout", type=int, default=900)
    ap.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if not os.path.exists(FLOW):
        sys.exit("chip_smoke: %s is missing: run this from a checkout of "
                 "the repository" % FLOW)
    sys.path.insert(0, HERE)
    if args.phase:
        return CHILD_PHASES[args.phase](args)

    from metaflow_tpu import device  # imports no JAX

    cache_dir = device.setup_compile_cache()  # children inherit it
    work = tempfile.mkdtemp(prefix="chip_smoke-")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    env["TPUFLOW_DATASTORE_SYSROOT_LOCAL"] = os.path.join(work, "ds")
    env["TPUFLOW_CLIENT_CACHE"] = os.path.join(work, "blobcache")
    log("size %s, chips %d, seed %d, compile cache %s"
        % (args.size, args.chips, args.seed,
           cache_dir if "JAX_COMPILATION_CACHE_DIR" in env
           else "off (CPU-pinned)"))

    if args.chips == 4:
        phases = [("multichip",
                   lambda: phase_child(args, env, "multichip"))]
    else:
        ref = {}

        def train():
            out = phase_train(args, env)
            ref["requests"] = out["reference"]
            return out

        phases = [
            ("train", train),
            ("serve", lambda: phase_serve(args, env, ref["requests"],
                                          paged=False)),
            ("paged", lambda: phase_serve(args, env, ref["requests"],
                                          paged=True)),
            ("kernels", lambda: phase_child(args, env, "kernels")),
        ]

    ok, dev = True, None
    try:
        for name, fn in phases:
            t0 = time.monotonic()
            try:
                dev = fn()["device"]
                log("phase %s: ok in %.1fs" % (name, time.monotonic() - t0))
            except (PhaseFailed, KeyError, OSError) as ex:
                ok = False
                log("phase %s: FAILED after %.1fs: %s: %s"
                    % (name, time.monotonic() - t0, type(ex).__name__, ex))
                if name == "train":
                    break  # nothing to serve
    finally:
        shutil.rmtree(work, ignore_errors=True)
    if dev is None or dev["platform"] != "tpu" or dev["count"] != args.chips:
        log("FAILED: wanted %d tpu device(s), the phases ran on %s"
            % (args.chips, dev))
        ok = False
    print(json.dumps({"ok": ok, "device": dev}), flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# child side: phases that use JAX themselves
# ---------------------------------------------------------------------------


def say(**facts):
    print(MARK + json.dumps(facts), flush=True)


def rel_err(got, want):
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-6))


class KernelChecks(object):
    """Results of kernels against references: relative errors by name,
    each held to KERNEL_REL_TOL."""

    def __init__(self):
        self.rel_err = {}
        self._last = time.perf_counter()

    def close(self, name, pairs):
        errs = {k: rel_err(g, w) for k, (g, w) in pairs.items()}
        self.rel_err[name] = errs
        now = time.perf_counter()
        log("kernel %s: %s (%.1fs with its reference and their compiles)"
            % (name, " ".join("%s=%.2e" % kv for kv in errs.items()),
               now - self._last))
        self._last = now
        bad = {k: e for k, e in errs.items()
               if not e <= KERNEL_REL_TOL}  # catches NaN too
        if bad:
            raise AssertionError(
                "kernel %s outside %s of its reference: %s"
                % (name, KERNEL_REL_TOL, bad))


def child_kernels(args):
    """Each kernel against a plain reference. On the TPU: compiled
    (interpret=False asserted) at real widths. CPU-pinned: interpreted
    at small shapes, and says so."""
    import math

    import jax
    import jax.numpy as jnp

    from metaflow_tpu import device
    import metaflow_tpu.ops.gmm  # noqa: F401  (ops.gmm is the function)
    from metaflow_tpu.ops.attention import (
        NEG_INF,
        flash_attention,
        flash_block_bwd,
        flash_block_fwd,
        reference_attention,
    )

    gmm_mod = sys.modules["metaflow_tpu.ops.gmm"]
    device.setup_compile_cache()
    compiles = device.watch_compiles()
    dev = device.describe()
    interpret = not device.on_tpu()
    if dev["platform"] == "tpu" and (interpret
                                     or gmm_mod._default_interpret()):
        raise RuntimeError("a kernel would run interpreted on the TPU")
    full = args.size != "tiny"
    if full and interpret:
        raise RuntimeError("real-width kernel checks need the TPU")
    log("kernels: interpret=%s (%s)" % (interpret, dev))
    key = jax.random.PRNGKey(args.seed)
    bf16 = jnp.bfloat16
    checks = KernelChecks()
    t_start = time.perf_counter()

    # flash attention forward + backward, Llama-3-8B head shapes
    B, S, H, KV, D = (1, 2048, 32, 8, 128) if full else (1, 256, 4, 2, 128)
    kq, kk, kv_, kg, key = jax.random.split(key, 5)
    q = jax.random.normal(kq, (B, S, H, D), bf16)
    k = jax.random.normal(kk, (B, S, KV, D), bf16)
    v = jax.random.normal(kv_, (B, S, KV, D), bf16)
    g = jax.random.normal(kg, (B, S, H, D), bf16)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True,
                                    interpret=interpret)

    def dense(q, k, v):
        return reference_attention(q, k, v, causal=True)

    def with_grads(fn):
        def run(q, k, v):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(g)
        return jax.jit(run)

    got, want = with_grads(flash)(q, k, v), with_grads(dense)(q, k, v)
    checks.close("flash[%d,%d,%dq/%dkv,%d]" % (B, S, H, KV, D),
                dict(zip(("out", "dq", "dk", "dv"), zip(got, want))))

    # the ring's flash block, diagonal and off-diagonal, fwd + bwd
    BH, S, D = (32, 2048, 128) if full else (4, 256, 128)
    scale = 1.0 / math.sqrt(D)
    kq, kk, kv_, kg, key = jax.random.split(key, 5)
    q, k, v, g = (jax.random.normal(kx, (BH, S, D), bf16)
                  for kx in (kq, kk, kv_, kg))

    def dense_block(q, k, v, diag):
        s = jnp.einsum("bqd,bkd->bqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        if diag:
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s,
                          NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return (jnp.einsum("bqk,bkd->bqd", p, v),
                jax.nn.logsumexp(s, axis=-1))

    for diag in (True, False):
        @jax.jit
        def block(q, k, v, g, diag=diag):
            acc, m, l = flash_block_fwd(q, k, v, scale, diag,
                                             interpret)
            out, lse = acc / l[..., None], m + jnp.log(l)
            delta = jnp.sum(g.astype(jnp.float32) * out, axis=-1)
            return (out, lse) + tuple(flash_block_bwd(
                q, k, v, g, lse, delta, scale, diag, interpret))

        @jax.jit
        def block_ref(q, k, v, g, diag=diag):
            (out, lse), vjp = jax.vjp(
                lambda q, k, v: dense_block(q, k, v, diag), q, k, v)
            return (out, lse) + vjp((g, jnp.zeros_like(lse)))

        checks.close(
            "flash_block[%d,%d,%d,%s]"
            % (BH, S, D, "diag" if diag else "off-diag"),
            dict(zip(("out", "lse", "dq", "dk", "dv"),
                     zip(block(q, k, v, g), block_ref(q, k, v, g)))))

    # grouped matmul fwd / dx / dw against a dense per-group matmul:
    # Mixtral-8x7B expert widths, and 64 experts of width 1024
    shapes = ([("mixtral-8x7b", 8, 4096, 14336, 8192),
               ("64x1024", 64, 2048, 1024, 16384)] if full
              else [("tiny", 4, 256, 256, 512)])
    for name, G, Dm, F, rows in shapes:
        @jax.jit
        def inputs(key, G=G, Dm=Dm, F=F, rows=rows):
            kx, kw, ki, kg = jax.random.split(key, 4)
            layout = gmm_mod.make_group_layout(
                jax.random.randint(ki, (rows,), 0, G), G)
            x = gmm_mod.scatter_rows(
                jax.random.normal(kx, (rows, Dm), bf16), layout)
            w = (jax.random.normal(kw, (G, Dm, F), jnp.float32)
                 * Dm ** -0.5).astype(bf16)
            # cotangents reach real rows only, as gather_rows' transpose
            # leaves the padding rows' at zero
            real = jnp.zeros((x.shape[0], 1), bf16).at[
                layout["dest"]].set(1)
            dy = jax.random.normal(kg, (x.shape[0], F), bf16) * real
            return (x, w, dy, layout["tile_group"], layout["tile_active"],
                    jnp.repeat(layout["tile_group"], gmm_mod.BLOCK_S))

        key, sub = jax.random.split(key)
        x, w, dy, tile_group, tile_active, row_group = inputs(sub)

        def grouped(x, w):
            return gmm_mod.gmm(x, w, tile_group, tile_active=tile_active,
                               interpret=interpret)

        def dense_groups(x, w):
            def one(y, gw):
                gi, wg = gw
                xg = jnp.where((row_group == gi)[:, None], x, 0)
                return y + jnp.dot(xg, wg,
                                   preferred_element_type=jnp.float32), None
            y, _ = jax.lax.scan(
                one, jnp.zeros((x.shape[0], F), jnp.float32),
                (jnp.arange(G), w))
            return y.astype(x.dtype)

        def fwd_bwd(fn):
            def run(x, w):
                y, vjp = jax.vjp(fn, x, w)
                return (y,) + vjp(dy)
            return jax.jit(run)

        got, want = fwd_bwd(grouped)(x, w), fwd_bwd(dense_groups)(x, w)
        checks.close("gmm[%s: %d rows, D %d, F %d, %d groups]"
                    % (name, x.shape[0], Dm, F, G),
                    dict(zip(("y", "dx", "dw"), zip(got, want))))
        del x, w, dy, got, want

    say(phase="kernels", device=dev, interpret=interpret, rel_err=checks.rel_err,
        tolerance=KERNEL_REL_TOL, compiles=compiles,
        seconds=time.perf_counter() - t_start,
        peak_bytes_in_use=device.peak_bytes_in_use())
    return 0


def child_multichip(args):
    """The sharded path, in one process that drives every chip: the
    train step on MeshSpec.fsdp_tp(2) against the same steps on a
    one-device mesh, the parameters really spread, and the sharded
    engine against the one-device engine."""
    import gc

    import jax
    import numpy as np

    from metaflow_tpu import device
    from metaflow_tpu.cmd.serve import build_engine
    from metaflow_tpu.models import llama
    from metaflow_tpu.serving import Request, Scheduler
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.training import (
        ResumableTokenBatches,
        make_trainer,
        memory_efficient_optimizer,
        shard_batch,
    )
    from tests.flows.chip_smoke_flow import (
        REQUESTS,
        smoke_config,
        zipf_corpus,
    )

    device.setup_compile_cache()
    compiles = device.watch_compiles()
    dev = device.describe()
    n = dev["count"]
    if n < 4:
        raise RuntimeError("the sharded path needs four devices, JAX "
                           "reports %s" % (dev,))
    if args.size != "tiny" and device.platform() != "tpu":
        raise RuntimeError("full-width smoke needs the TPU")
    cfg = smoke_config(args.size, 4 if args.layers is None else args.layers)
    seq = 128 if args.size == "tiny" else 2048
    batch, steps = 4, 4
    corpus = zipf_corpus(cfg.vocab_size, (steps + 1) * batch * (seq + 1),
                         args.seed)

    def in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.local_devices()[:4]]

    def train(mesh):
        state, step, _ = make_trainer(
            jax.random.PRNGKey(args.seed), cfg, mesh, llama,
            optimizer=memory_efficient_optimizer(
                lr=3e-4, warmup_steps=1, total_steps=steps))
        jax.block_until_ready(state)
        placed = in_use()
        state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
        spread = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                state["params"]):
            if leaf.size >= 1 << 20:
                spread[jax.tree_util.keystr(path)] = sorted(
                    {s.device.id for s in leaf.addressable_shards
                     if s.data.size < leaf.size})
        losses = []
        stream = iter(ResumableTokenBatches(corpus, batch, seq,
                                            seed=args.seed))
        for _ in range(steps):
            state, metrics = step(
                state, shard_batch({"tokens": next(stream)["tokens"]},
                                   mesh))
            losses.append(float(metrics["loss"]))
        return state, losses, placed, spread, state_bytes

    t0 = time.perf_counter()
    mesh4 = create_mesh(MeshSpec.fsdp_tp(2), n_devices=4)
    state, losses4, placed4, spread, state_bytes = train(mesh4)
    log("multichip: mesh %s, losses %s" % (dict(mesh4.shape), losses4))
    log("multichip: state %d bytes, bytes_in_use per device after init %s"
        % (state_bytes, placed4))
    not_spread = {k: v for k, v in spread.items() if len(v) != 4}
    if not_spread:
        raise AssertionError("large leaves not sharded over four "
                             "devices: %s" % (not_spread,))
    if all(b is not None for b in placed4):  # XLA:CPU reports none
        off = [b for b in placed4
               if not 0.2 * state_bytes <= b <= 0.3 * state_bytes]
        if off:
            raise AssertionError(
                "per-device bytes are not near a quarter of the state's "
                "%d: %s" % (state_bytes, placed4))
    del state
    gc.collect()

    mesh1 = create_mesh(MeshSpec.dp(), n_devices=1)
    state, losses1, _, _, _ = train(mesh1)
    log("multichip: one device, losses %s" % (losses1,))
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses4, losses1))
    if not (np.all(np.isfinite(losses4)) and worst <= SHARDED_LOSS_REL_TOL):
        raise AssertionError(
            "sharded losses %s differ from one-device losses %s by %.3g "
            "(tolerance %s)" % (losses4, losses1, worst,
                                SHARDED_LOSS_REL_TOL))
    train_s = time.perf_counter() - t0

    # serve the one-device run's weights from both layouts
    t0 = time.perf_counter()
    params = state["params"]
    del state
    gc.collect()
    rng = np.random.default_rng(args.seed + 1)
    prompts = [(rng.integers(1, cfg.vocab_size, p).tolist(), m)
               for p, m in REQUESTS[:4]]

    def decode(engine):
        sched = Scheduler(engine)
        reqs = [Request(list(p), max_new_tokens=m, rng=0)
                for p, m in prompts]
        for r in reqs:
            sched.submit(r)
        sched.run_until_idle(1_000_000)
        return [r.result(timeout=5) for r in reqs]

    kw = dict(slots=4, max_seq_len=1024, prefill_chunk=64)
    one = decode(build_engine(params, cfg, **kw))
    sharded_engine = build_engine(params, cfg, mesh_spec="fsdp_tp", **kw)
    on = sorted({s.device.id for s in
                 sharded_engine.params["lm_head"].addressable_shards})
    four = decode(sharded_engine)
    if len(on) != 4:
        raise AssertionError("the sharded engine's lm_head is on devices "
                             "%s" % (on,))

    def gap(prompt, new, i, token):
        """How far below the winner `token` sat in the one-device logits
        that chose new[i] (tensor-parallel sums run in another order)."""
        from metaflow_tpu.inference import decode_forward, init_kv_cache

        seq_tokens = np.zeros((1, 512), np.int32)
        seq_tokens[0, :len(prompt) + i] = prompt + new[:i]
        logits, _ = jax.jit(
            lambda p, t: decode_forward(p, t, init_kv_cache(cfg, 1, 1024),
                                        0, cfg))(params, seq_tokens)
        row = np.asarray(logits[0, len(prompt) + i - 1], np.float32)
        return float(row.max() - row[token])

    verdicts = []
    for (prompt, n_new), a, b in zip(prompts, one, four):
        if a == b:
            verdicts.append("equal")
            continue
        i = next(j for j in range(n_new) if a[j] != b[j])
        g = gap(prompt, a, i, b[i])
        if len(b) != n_new or g > NEAR_TIE_LOGIT_TOL:
            raise AssertionError(
                "prompt of %d: the sharded engine's token %d is %d, the "
                "one-device engine's %d (logit gap %.4f, tolerance %s)"
                % (len(prompt), i, b[i], a[i], g, NEAR_TIE_LOGIT_TOL))
        verdicts.append("near tie at token %d (gap %.4f)" % (i, g))
    log("multichip: %d requests, sharded engine (lm_head on devices %s) "
        "against the one-device engine: %s"
        % (len(one), on, "; ".join(verdicts)))
    del sharded_engine, params
    gc.collect()
    t0 = time.perf_counter()
    kernel_errs = sharded_kernels(args, interpret=not device.on_tpu())
    kernels_s = time.perf_counter() - t0
    say(phase="multichip", device=dev, losses_sharded=losses4,
        sharded_kernels=kernel_errs, sharded_kernels_s=kernels_s,
        losses_one_device=losses1, loss_rel_diff=worst,
        tolerance=SHARDED_LOSS_REL_TOL, state_bytes=state_bytes,
        bytes_in_use_per_device=placed4,
        large_leaves_on_four_devices=len(spread), train_s=train_s,
        serve_s=time.perf_counter() - t0, compiles=compiles,
        peak_bytes_in_use=device.peak_bytes_in_use())
    return 0


def sharded_kernels(args, interpret):
    """The kernels that exist only across chips, against one-device
    references: ring attention (the flash block under shard_map, KV
    rotating over 'sequence') forward and backward, and the dropless
    expert-parallel MoE layer (all-to-all in, grouped matmul, all-to-all
    back) at Mixtral-8x7B widths against the dense dispatch."""
    import jax
    import jax.numpy as jnp

    from metaflow_tpu.ops.attention import reference_attention
    from metaflow_tpu.ops.moe import moe_ffn
    from metaflow_tpu.ops.ring_attention import ring_attention
    from metaflow_tpu.spmd import MeshSpec, create_mesh

    full = args.size != "tiny"
    bf16 = jnp.bfloat16
    key = jax.random.PRNGKey(args.seed + 2)
    checks = KernelChecks()

    B, S, H, KV, D = (1, 4096, 32, 8, 128) if full else (1, 512, 4, 2, 128)
    mesh = create_mesh(MeshSpec({"sequence": 4}), n_devices=4)
    kq, kk, kv_, kg, key = jax.random.split(key, 5)
    q = jax.random.normal(kq, (B, S, H, D), bf16)
    k = jax.random.normal(kk, (B, S, KV, D), bf16)
    v = jax.random.normal(kv_, (B, S, KV, D), bf16)
    g = jax.random.normal(kg, (B, S, H, D), bf16)

    def with_grads(fn):
        def run(q, k, v):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(g)
        return jax.jit(run)

    ring_impl = "flash_interpret" if interpret else "flash"
    got = with_grads(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=True, impl=ring_impl))(q, k, v)
    want = with_grads(lambda q, k, v: reference_attention(
        q, k, v, causal=True))(q, k, v)
    checks.close("ring_flash[%d,%d,%dq/%dkv,%d over 4]"
                % (B, S, H, KV, D),
                dict(zip(("out", "dq", "dk", "dv"), zip(got, want))))
    del q, k, v, g, got, want

    # few tokens: the dense oracle runs every expert on every token, in
    # f32, on one device
    Bm, Sm, E, F, n_exp = ((4, 256, 4096, 14336, 8) if full
                           else (4, 128, 128, 256, 4))
    mesh = create_mesh(MeshSpec.moe(expert=4), n_devices=4)
    kx, kr, k1, k2, k3, kg = jax.random.split(key, 6)
    x = jax.random.normal(kx, (Bm, Sm, E), bf16)
    dy = jax.random.normal(kg, (Bm, Sm, E), bf16)
    router = (jax.random.normal(kr, (E, n_exp), jnp.float32)
              * E ** -0.5).astype(bf16)
    w_gate, w_up = ((jax.random.normal(kw, (n_exp, E, F), jnp.float32)
                     * E ** -0.5).astype(bf16) for kw in (k1, k2))
    w_down = (jax.random.normal(k3, (n_exp, F, E), jnp.float32)
              * F ** -0.5).astype(bf16)

    def layer(dispatch, **kw):
        def run(x, w_gate, w_up, w_down):
            out, vjp = jax.vjp(
                lambda *a: moe_ffn(a[0], router, *a[1:],
                                   num_experts_per_tok=2,
                                   dispatch=dispatch, **kw)[0],
                x, w_gate, w_up, w_down)
            return (out,) + vjp(dy)
        return jax.jit(run)

    got = layer("gmm_ep", mesh=mesh)(x, w_gate, w_up, w_down)
    want = layer("dense")(x, w_gate, w_up, w_down)
    checks.close("gmm_ep[%d tokens, D %d, F %d, %d experts over 4]"
                % (Bm * Sm, E, F, n_exp),
                dict(zip(("y", "dx", "dw_gate", "dw_up", "dw_down"),
                         zip(got, want))))
    return checks.rel_err


CHILD_PHASES = {"kernels": child_kernels, "multichip": child_multichip}


if __name__ == "__main__":
    sys.exit(main())
