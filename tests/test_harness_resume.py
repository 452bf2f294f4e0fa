"""Generative resume_* tests over the harness's graphs (tests/harness.py)."""

import re

import pytest

from harness import ActiveContext, GRAPHS, _check_run, generate_flow

# resume: fail a mid-graph step on the first run, resume, verify the clone
# + re-execution boundary (reference: test/core resume_* tests). The gang
# case resumes INTO a partially-done gang: only rank 1 failed, other ranks'
# task datastores are complete, and resume must re-run the gang as a unit.
RESUME_CASES = [
    ("linear", "b"),
    ("foreach", "body"),
    ("nested_foreach", "leaf"),
    ("branch", "j"),
    ("gang", "train"),
    # a gang INSIDE a foreach: resume must re-run only the failed
    # iteration's gang as a unit
    ("foreach_gang", "train"),
    # failing AFTER the loop: every recursion iteration must clone
    ("recursive", "done"),
]

# resume under every scheduler-execution context: the fork pool (default),
# no-fork workers, and the warm daemon — clone/re-run boundaries must not
# depend on HOW tasks are launched
RESUME_CONTEXTS = ("default", "exec_workers", "daemon")


@pytest.mark.parametrize(
    "graph_name,fail_step,context_name",
    [(g, s, c) for (g, s) in RESUME_CASES for c in RESUME_CONTEXTS],
)
def test_generated_resume(graph_name, fail_step, context_name, run_flow,
                          tpuflow_root, tmp_path):
    graph = GRAPHS[graph_name]
    flow_name = "Res%s%s%sFlow" % (
        graph_name.title().replace("_", ""), fail_step.title(),
        context_name.title().replace("_", ""),
    )
    src = generate_flow(graph, flow_name, fail_step=fail_step)
    flow_file = str(tmp_path / ("%s.py" % flow_name))
    with open(flow_file, "w") as f:
        f.write(src)

    with ActiveContext(context_name, tpuflow_root) as ctx:
        env = dict(ctx.env)
        env["FAIL_ONCE"] = "1"
        proc = run_flow(flow_file, *(ctx.args + ["run"]), env_extra=env,
                        prefix=ctx.prefix, expect_fail=True)
        assert "induced failure" in proc.stdout + proc.stderr

        proc = run_flow(flow_file, *(ctx.args + ["resume"]),
                        env_extra=ctx.env, prefix=ctx.prefix)
        out = proc.stdout + proc.stderr
        assert "TRACE:" in proc.stdout
        # a NONZERO clone count: steps before the failure must clone, not
        # re-run
        m = re.search(r"\((\d+) tasks? run, (\d+) cloned\)", out)
        assert m and int(m.group(2)) > 0, out

        _check_run(flow_name, graph, tpuflow_root, ctx.client_env)
