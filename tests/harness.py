"""Mini generative test harness: graphs × contexts.

Reference model: test/core (SURVEY.md §4) — orthogonal graph shapes and
execution contexts are combined, a real flow file is code-generated for each
combination, executed through the actual CLI, and checked via the client
API. This multiplies coverage across the DSL/scheduler/datastore layers.
"""

import contextlib
import os

GRAPHS = {
    "linear": [
        {"name": "start", "next": ["a"]},
        {"name": "a", "next": ["b"]},
        {"name": "b", "next": ["end"]},
        {"name": "end"},
    ],
    "branch": [
        {"name": "start", "next": ["a", "b"]},
        {"name": "a", "next": ["j"]},
        {"name": "b", "next": ["j"]},
        {"name": "j", "join": True, "next": ["end"]},
        {"name": "end"},
    ],
    "foreach": [
        {"name": "start", "foreach": 3, "next": ["body"]},
        {"name": "body", "next": ["j"]},
        {"name": "j", "join": True, "next": ["end"]},
        {"name": "end"},
    ],
    "nested_foreach": [
        {"name": "start", "foreach": 2, "next": ["mid"]},
        {"name": "mid", "foreach": 2, "next": ["leaf"]},
        {"name": "leaf", "next": ["ji"]},
        {"name": "ji", "join": True, "next": ["jo"]},
        {"name": "jo", "join": True, "next": ["end"]},
        {"name": "end"},
    ],
    "branch_of_foreach": [
        {"name": "start", "next": ["p", "q"]},
        {"name": "p", "foreach": 2, "next": ["pb"]},
        {"name": "pb", "next": ["pj"]},
        {"name": "pj", "join": True, "next": ["j"]},
        {"name": "q", "next": ["j"]},
        {"name": "j", "join": True, "next": ["end"]},
        {"name": "end"},
    ],
    "switch": [
        {"name": "start", "switch": {"left": "a", "right": "b"},
         "condition_value": "right", "next": ["a", "b"]},
        {"name": "a", "next": ["done"]},
        {"name": "b", "next": ["done"]},
        {"name": "done", "next": ["end"]},
        {"name": "end"},
    ],
    "gang": [
        {"name": "start", "num_parallel": 3, "next": ["train"]},
        {"name": "train", "next": ["j"]},
        {"name": "j", "join": True, "next": ["end"]},
        {"name": "end"},
    ],
    # a gang fanned out by a foreach (hyperparameter sweep of gang-trained
    # models): on Argo every iteration must create its own JobSet
    "foreach_gang": [
        {"name": "start", "foreach": 2, "next": ["prep"]},
        {"name": "prep", "num_parallel": 2, "next": ["train"]},
        {"name": "train", "next": ["gj"]},
        {"name": "gj", "join": True, "next": ["oj"]},
        {"name": "oj", "join": True, "next": ["end"]},
        {"name": "end"},
    ],
    # recursion via switch back-edge: work+check iterate loop_counter
    # times, then the exit case runs (reference: test/core recursive
    # graph shapes)
    "recursive": [
        {"name": "start", "next": ["work"]},
        {"name": "work", "next": ["check"]},
        {"name": "check", "switch": {"again": "work", "stop": "done"},
         "loop_counter": 3, "loop_case": "again", "exit_case": "stop",
         "next": ["work", "done"]},
        {"name": "done", "next": ["end"]},
        {"name": "end"},
    ],
}

# execution contexts: CLI/env/provider variations every graph must survive.
# kind 'plain' needs no services; 'gs' runs against a fake GCS server (the
# whole artifact path rides HTTP); 'service' points metadata at the REST
# reference service (reference: test/core/contexts.json varies datastore and
# metadata providers the same way)
CONTEXTS = {
    "default": {"kind": "plain", "args": [], "env": {}},
    "exec_workers": {"kind": "plain", "args": [],
                     "env": {"TPUFLOW_FORK_WORKERS": "0"}},
    "with_retry": {
        "kind": "plain",
        "args": ["--with", "retry:times=1,minutes_between_retries=0"],
        "env": {},
    },
    "gs_storage": {"kind": "gs", "args": [], "env": {}},
    "service_metadata": {"kind": "service", "args": [], "env": {}},
    "daemon": {"kind": "daemon", "args": [], "env": {}},
}


class ActiveContext(object):
    """Starts whatever servers a context needs; yields run args/env and the
    matching client-side env so the checker reads through the same
    providers the flow wrote through."""

    def __init__(self, name, tpuflow_root):
        self.name = name
        self.spec = CONTEXTS[name]
        self.root = tpuflow_root
        self.args = list(self.spec["args"])
        self.env = dict(self.spec["env"])
        self.client_env = {}
        self.prefix = None  # extra interpreter args before the flow file
        self._cleanups = []

    def __enter__(self):
        kind = self.spec["kind"]
        if kind == "gs":
            from fake_gcs import FakeGCSServer

            srv = FakeGCSServer().__enter__()
            self._cleanups.append(lambda: srv.__exit__(None, None, None))
            self.args += ["--datastore", "gs",
                          "--datastore-root", "gs://harness-bucket/root"]
            self.env["TPUFLOW_GS_ENDPOINT"] = srv.endpoint
            self.client_env = {
                "TPUFLOW_GS_ENDPOINT": srv.endpoint,
                "TPUFLOW_DEFAULT_DATASTORE": "gs",
                "TPUFLOW_DATASTORE_SYSROOT_GS": "gs://harness-bucket/root",
            }
        elif kind == "service":
            from metaflow_tpu.metadata import MetadataService

            svc = MetadataService(self.root)
            svc.start()
            self._cleanups.append(svc.stop)
            self.args += ["--metadata", "service"]
            self.env["TPUFLOW_SERVICE_URL"] = svc.url
            self.client_env = {
                "TPUFLOW_SERVICE_URL": svc.url,
                "TPUFLOW_DEFAULT_METADATA": "service",
            }
        elif kind == "daemon":
            # runs ride the warm scheduler daemon over its unix socket:
            # `python -m metaflow_tpu.daemon run flow.py run ...`
            import subprocess
            import sys
            import time

            os.makedirs(self.root, exist_ok=True)
            sock = os.path.join(self.root, "daemon.sock")
            env = dict(os.environ)
            env["TPUFLOW_DAEMON_SOCKET"] = sock
            env["TPUFLOW_DATASTORE_SYSROOT_LOCAL"] = self.root
            env["JAX_PLATFORMS"] = "cpu"
            env["JAX_PLATFORM_NAME"] = "cpu"
            env["PYTHONPATH"] = os.pathsep.join(
                [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
                + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p]
            )
            proc = subprocess.Popen(
                [sys.executable, "-m", "metaflow_tpu.daemon", "start"],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )

            def _stop():
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()

            self._cleanups.append(_stop)
            from metaflow_tpu.daemon import ping

            deadline = time.time() + 30
            while time.time() < deadline:
                if ping(sock_path=sock):
                    break
                time.sleep(0.2)
            else:
                _stop()
                raise RuntimeError("scheduler daemon did not come up")
            self.prefix = ["-m", "metaflow_tpu.daemon", "run"]
            self.env["TPUFLOW_DAEMON_SOCKET"] = sock
        return self

    def __exit__(self, *exc):
        for fn in reversed(self._cleanups):
            fn()
        return False


def expected_task_counts(graph):
    """Cardinality of each step given the template's foreach sizes."""
    by_name = {s["name"]: s for s in graph}
    counts = {}

    def visit(name, multiplier):
        spec = by_name[name]
        counts[name] = counts.get(name, 0) + multiplier
        child_mult = multiplier * spec.get("foreach", 1) \
            * spec.get("num_parallel", 1)
        if spec.get("switch"):
            if spec.get("loop_counter"):
                # the switch and its back-edge target each run
                # loop_counter times; one pass was already counted on the
                # way in, so add the remaining K-1 before taking the exit
                k = spec["loop_counter"]
                back = spec["switch"][spec["loop_case"]]
                counts[name] += multiplier * (k - 1)
                counts[back] = counts.get(back, 0) + multiplier * (k - 1)
                visit(spec["switch"][spec["exit_case"]], child_mult)
                return
            # only the chosen case executes
            chosen = spec["switch"][spec["condition_value"]]
            visit(chosen, child_mult)
            return
        for child in spec.get("next", []):
            if by_name[child].get("join"):
                continue  # joins handled once per join instance
            visit(child, child_mult)

    visit("start", 1)
    # joins: one task per instance of the *parent* split level
    changed = True
    while changed:
        changed = False
        for spec in graph:
            if not spec.get("join") or spec["name"] in counts:
                continue
            # a join's count = count of the split ancestor that opened the
            # level being joined = count of its in-step divided by the
            # foreach factor of the innermost split
            in_steps = [
                s for s in graph if spec["name"] in s.get("next", [])
            ]
            if not all(s["name"] in counts for s in in_steps):
                continue
            # innermost split parent's multiplier:
            inner = min(counts[s["name"]] for s in in_steps)
            # dividing by the foreach factor: find the split that fans into
            # this join's inputs
            split = _innermost_split(graph, spec["name"])
            factor = (
                by_name[split].get(
                    "foreach",
                    by_name[split].get("num_parallel",
                                       len(by_name[split].get("next", []))),
                )
                if split else 1
            )
            counts[spec["name"]] = max(1, inner // factor)
            changed = True
            # propagate beyond the join
            for child in spec.get("next", []):
                if not by_name[child].get("join"):
                    visit(child, counts[spec["name"]])
    return counts


def _innermost_split(graph, join_name):
    """Walk backwards from the join to the split it closes (templates here
    are simple enough for a stack walk)."""
    by_name = {s["name"]: s for s in graph}
    # DFS from start tracking the open-split stack
    result = {}

    def walk(name, stack):
        spec = by_name[name]
        if spec.get("join"):
            if stack:
                result.setdefault(name, stack[-1])
                stack = stack[:-1]
        elif spec.get("switch"):
            # a switch executes ONE branch: no split level opened. A
            # recursive switch's back-edge is not walked (the stack walk
            # is about split levels, and looping would never terminate).
            if spec.get("loop_counter"):
                walk(spec["switch"][spec["exit_case"]], stack)
                return
        elif (spec.get("foreach") or spec.get("num_parallel")
              or len(spec.get("next", [])) > 1):
            stack = stack + [name]
        for child in spec.get("next", []):
            walk(child, stack)

    walk("start", [])
    return result.get(join_name)


def generate_flow(graph, flow_name, fail_step=None, specs=()):
    """Emit a runnable flow file for a graph template. Each task appends its
    step name to a 'trace' artifact; joins merge traces.

    fail_step: that step raises while env FAIL_ONCE=1 (resume tests). In a
    gang step only rank 1 fails — so the first run leaves the gang
    partially done (other ranks wrote their datastores) and `resume` must
    re-run it as a unit.

    specs: Spec instances (tests/specs.py — the harness's orthogonal
    "tests" axis, reference MetaflowTest pattern): each contributes
    flow-level lines, per-step-kind decorators and body lines. Body lines
    inject after the trace bookkeeping and before control flow (for `end`
    steps: after the TRACE print, so a spec may raise under @catch
    without losing the trace)."""
    from specs import step_kind

    lines = [
        "import os",
        "",
        "import metaflow_tpu",
        "from metaflow_tpu import FlowSpec, Parameter, current, step",
        "",
        "",
        "class %s(FlowSpec):" % flow_name,
    ]
    for sp in specs:
        lines += ["    %s" % l for l in sp.param_lines]
    for spec in graph:
        name = spec["name"]
        kind = step_kind(spec)
        args = "(self, inputs)" if spec.get("join") else "(self)"
        for sp in specs:
            for deco in (sp.decorators.get("all", [])
                         + sp.decorators.get(kind, [])):
                lines.append("    %s" % deco)
        lines.append("    @step")
        lines.append("    def %s%s:" % (name, args))
        if name == fail_step:
            in_gang = any(
                name in s.get("next", []) and s.get("num_parallel")
                for s in graph
            )
            cond = "os.environ.get('FAIL_ONCE') == '1'"
            if in_gang:
                cond += " and current.parallel.node_index == 1"
            lines.append("        if %s:" % cond)
            lines.append(
                "            raise Exception('induced failure in %s')" % name
            )
        if spec.get("join"):
            lines.append(
                "        self.trace = sorted(set(sum((i.trace for i in "
                "inputs), [])))"
            )
            lines.append("        self.trace = self.trace + [%r]" % name)
        elif name == "start":
            lines.append("        self.trace = [%r]" % name)
        else:
            lines.append("        self.trace = self.trace + [%r]" % name)
        if kind != "end":
            for sp in specs:
                lines += ["        %s" % l
                          for l in sp.lines(kind, spec, graph)]
        if spec.get("switch"):
            if spec.get("loop_counter"):
                # data-dependent recursion: iterate until the counter
                # (carried as an artifact across iterations) hits K
                lines.append(
                    "        self.loop_n = getattr(self, 'loop_n', 0) + 1"
                )
                lines.append(
                    "        self.choice = %r if self.loop_n < %d else %r"
                    % (spec["loop_case"], spec["loop_counter"],
                       spec["exit_case"])
                )
            else:
                lines.append("        self.choice = %r"
                             % spec["condition_value"])
            cases = ", ".join(
                "%r: self.%s" % (k, v) for k, v in spec["switch"].items()
            )
            lines.append("        self.next({%s}, condition='choice')"
                         % cases)
        elif spec.get("num_parallel"):
            lines.append("        self.next(self.%s, num_parallel=%d)"
                         % (spec["next"][0], spec["num_parallel"]))
        elif spec.get("foreach"):
            lines.append("        self.items = list(range(%d))"
                         % spec["foreach"])
            lines.append("        self.next(self.%s, foreach='items')"
                         % spec["next"][0])
        elif spec.get("next"):
            lines.append(
                "        self.next(%s)"
                % ", ".join("self.%s" % n for n in spec["next"])
            )
        else:
            lines.append("        print('TRACE:', ','.join(self.trace))")
            for sp in specs:
                lines += ["        %s" % l
                          for l in sp.lines(kind, spec, graph)]
        lines.append("")
    lines.append("")
    lines.append("if __name__ == '__main__':")
    lines.append("    %s()" % flow_name)
    return "\n".join(lines)



@contextlib.contextmanager
def _client_env(extra):
    saved = {k: os.environ.get(k) for k in extra}
    os.environ.update(extra)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _check_run(flow_name, graph, tpuflow_root, client_env):
    """Client-side checker: every step ran with the expected cardinality,
    read back through the same providers the flow wrote through."""
    os.environ["TPUFLOW_DATASTORE_SYSROOT_LOCAL"] = tpuflow_root
    with _client_env(client_env):
        from metaflow_tpu import client

        client.namespace(None)
        run = client.Flow(flow_name).latest_run
        assert run.successful
        expected = expected_task_counts(graph)
        for step_name, count in expected.items():
            tasks = list(run[step_name].tasks())
            assert len(tasks) == count, (
                "%s/%s: expected %d tasks, found %d"
                % (flow_name, step_name, count, len(tasks))
            )
        # the end task saw every step that executed (unchosen switch
        # branches never run)
        trace = run.data.trace
        assert set(trace) == {n for n, c in expected.items() if c > 0}, trace


# The graphs x contexts product (reference: test/README.md runs every graph
# under every valid context) is spread over three test files by graph, so
# that `--dist loadfile` can give them to three workers; between them the
# groups hold every graph (test_harness_graphs_simple.py checks it).
GRAPH_GROUPS = {
    "simple": ("branch", "foreach", "linear", "switch"),
    "nested": ("branch_of_foreach", "nested_foreach", "recursive"),
    "gang": ("foreach_gang", "gang"),
}


def matrix(group):
    return [(g, c) for g in GRAPH_GROUPS[group] for c in sorted(CONTEXTS)]


def run_generated_flow(graph_name, context_name, run_flow, tpuflow_root,
                       tmp_path):
    """One cell of the matrix: generate the flow, run it through the CLI
    under the context, check it through the client."""
    graph = GRAPHS[graph_name]
    flow_name = "Gen%s%sFlow" % (
        graph_name.title().replace("_", ""),
        context_name.title().replace("_", ""),
    )
    src = generate_flow(graph, flow_name)
    flow_file = str(tmp_path / ("%s.py" % flow_name))
    with open(flow_file, "w") as f:
        f.write(src)

    with ActiveContext(context_name, tpuflow_root) as ctx:
        proc = run_flow(flow_file, *(ctx.args + ["run"]), env_extra=ctx.env,
                        prefix=ctx.prefix)
        assert "TRACE:" in proc.stdout
        _check_run(flow_name, graph, tpuflow_root, ctx.client_env)
