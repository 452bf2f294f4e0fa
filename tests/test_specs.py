"""Specs × graphs × contexts: the harness's orthogonal "tests" axis
(VERDICT r4 missing #4; reference mechanism test/README.md:60-140).

Additive specs stack into ONE generated flow per graph — a single run
exercises all of them (artifact propagation, merge-conflict detection,
foreach_stack, tag mutation, parameter visibility, attempt_ok metadata,
heartbeat, cards) — so the matrix grows as specs × graphs while the
runtime stays linear in graphs. The execution context rotates
deterministically per graph, covering every context across the graph
set. Control-flow specs (catch+retry) and resume-from-every-step run
their own flows.
"""

import contextlib
import os

import pytest

from harness import (
    ActiveContext,
    CONTEXTS,
    GRAPHS,
    _check_run,
    _client_env,
    expected_task_counts,
    generate_flow,
)
from specs import ADDITIVE_SPECS, SOLO_SPECS

# deterministic context rotation: every context is exercised across the
# graph set without multiplying runtime by |contexts|
_SORTED_GRAPHS = sorted(GRAPHS)
_SORTED_CONTEXTS = sorted(CONTEXTS)


def _rotated_context(graph_name):
    return _SORTED_CONTEXTS[
        _SORTED_GRAPHS.index(graph_name) % len(_SORTED_CONTEXTS)]


@contextlib.contextmanager
def _client_run(flow_name, client_env):
    """Yield the latest run WITH the provider env still active — spec
    checkers read task datastores lazily (a gs-context check would
    otherwise lose its endpoint credentials)."""
    with _client_env(client_env):
        from metaflow_tpu import client

        client.namespace(None)
        yield client.Flow(flow_name).latest_run


@pytest.mark.parametrize("graph_name", _SORTED_GRAPHS)
def test_spec_stack(graph_name, run_flow, tpuflow_root, tmp_path):
    context_name = _rotated_context(graph_name)
    specs = [s for s in ADDITIVE_SPECS
             if s.contexts is None or context_name in s.contexts]
    graph = GRAPHS[graph_name]
    flow_name = "Spec%sFlow" % graph_name.title().replace("_", "")
    src = generate_flow(graph, flow_name, specs=specs)
    flow_file = str(tmp_path / ("%s.py" % flow_name))
    with open(flow_file, "w") as f:
        f.write(src)

    pre = [a for s in specs for a in s.pre_args]
    extra = [a for s in specs for a in s.extra_args]
    with ActiveContext(context_name, tpuflow_root) as ctx:
        run_flow(flow_file, *(ctx.args + pre + ["run"] + extra),
                 env_extra=ctx.env, prefix=ctx.prefix)
        _check_run(flow_name, graph, tpuflow_root, ctx.client_env)
        counts = expected_task_counts(graph)
        with _client_run(flow_name, ctx.client_env) as run:
            for s in specs:
                s.check(run, graph, counts, ctx.env)


@pytest.mark.parametrize(
    "spec,graph_name",
    [(s, g) for s in SOLO_SPECS for g in _SORTED_GRAPHS
     if g not in s.skip_graphs],
    ids=lambda v: getattr(v, "name", v),
)
def test_spec_solo(spec, graph_name, run_flow, tpuflow_root, tmp_path):
    context_name = (spec.contexts or ("default",))[0]
    graph = GRAPHS[graph_name]
    flow_name = "Solo%s%sFlow" % (
        spec.name.title().replace("_", ""),
        graph_name.title().replace("_", ""),
    )
    src = generate_flow(graph, flow_name, specs=[spec])
    flow_file = str(tmp_path / ("%s.py" % flow_name))
    with open(flow_file, "w") as f:
        f.write(src)

    with ActiveContext(context_name, tpuflow_root) as ctx:
        run_flow(flow_file, *(ctx.args + list(spec.pre_args) + ["run"]
                              + list(spec.extra_args)),
                 env_extra=ctx.env, prefix=ctx.prefix)
        with _client_run(flow_name, ctx.client_env) as run:
            spec.check(run, graph, expected_task_counts(graph), ctx.env)


# resume-from-EVERY-step (not just the sampled RESUME_CASES): fail each
# non-start step of the linear and foreach graphs in turn, resume, and
# require a clean finish with a nonzero clone count
_RESUME_EVERYWHERE = [
    (g, s["name"])
    for g in ("linear", "foreach")
    for s in GRAPHS[g]
    if s["name"] != "start"
]


@pytest.mark.parametrize("graph_name,fail_step", _RESUME_EVERYWHERE)
def test_resume_from_every_step(graph_name, fail_step, run_flow,
                                tpuflow_root, tmp_path):
    import re

    graph = GRAPHS[graph_name]
    flow_name = "Rev%s%sFlow" % (
        graph_name.title().replace("_", ""), fail_step.title())
    src = generate_flow(graph, flow_name, fail_step=fail_step)
    flow_file = str(tmp_path / ("%s.py" % flow_name))
    with open(flow_file, "w") as f:
        f.write(src)

    with ActiveContext("default", tpuflow_root) as ctx:
        env = dict(ctx.env)
        env["FAIL_ONCE"] = "1"
        proc = run_flow(flow_file, *(ctx.args + ["run"]), env_extra=env,
                        prefix=ctx.prefix, expect_fail=True)
        assert "induced failure" in proc.stdout + proc.stderr

        proc = run_flow(flow_file, *(ctx.args + ["resume"]),
                        env_extra=ctx.env, prefix=ctx.prefix)
        out = proc.stdout + proc.stderr
        assert "TRACE:" in proc.stdout
        m = re.search(r"\((\d+) tasks? run, (\d+) cloned\)", out)
        assert m and int(m.group(2)) > 0, out
        _check_run(flow_name, graph, tpuflow_root, ctx.client_env)
