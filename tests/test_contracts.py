"""Contracts analyzer (metaflow_tpu/analysis/contracts.py) + knob
registry (metaflow_tpu/knobs.py).

Seeded-violation fixtures assert each of the seven contract finding
kinds fires with the right file:line; the library self-scan asserts the
full sweep (knob lint + deadline lattice + telemetry drift, both
directions) is CLEAN over metaflow_tpu/ — which is also the
migration-completeness gate: a new raw ``os.environ["TPUFLOW_*"]`` read
anywhere outside knobs.py fails tier-1 here.
"""

import json
import os
import subprocess
import sys

import pytest

from metaflow_tpu import FlowSpec, knobs, step
from metaflow_tpu.analysis import (
    AnalysisError,
    analyze_contracts,
    analyze_flow,
    pre_run_gate,
)
from metaflow_tpu.analysis.contracts import (
    CONTRACT_FINDING_CODES,
    analyze_library,
    deadline_order,
    knob_lint,
    load_pins,
    scan_paths,
    scan_source,
    telemetry_drift,
)
from metaflow_tpu.graph import FlowGraph

import schema_validate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIBRARY = os.path.join(REPO, "metaflow_tpu")
SCHEMA_PATH = os.path.join(REPO, "tests", "schema_validate.py")
DOCS_PATH = os.path.join(REPO, "docs", "knobs.md")


def _marker_line(src, marker):
    """1-based line number of the (first) source line containing marker."""
    for i, line in enumerate(src.splitlines(), 1):
        if marker in line:
            return i
    raise AssertionError("marker %r not in fixture" % marker)


def _lint_fixture(tmp_path, src, docs_text=None):
    path = tmp_path / "fixture.py"
    path.write_text(src)
    reads, accessors, _emits = scan_paths([str(path)])
    return str(path), knob_lint(reads, accessors, docs_text=docs_text)


# ---------------------------------------------------------------------------
# seeded violations: the four knob finding kinds
# ---------------------------------------------------------------------------


def test_knob_unregistered_raw_read(tmp_path):
    src = (
        "import os\n"
        "def f(env):\n"
        "    a = os.environ.get('TPUFLOW_HANG_FLOOR_S', '60')  # MARK-GET\n"
        "    b = os.environ['TPUFLOW_DEBUG']  # MARK-SUBSCRIPT\n"
        "    c = 'TPUFLOW_SANITIZE' in os.environ  # MARK-IN\n"
        "    d = env.get('TPUFLOW_HANG_POLL_S')  # MARK-ENVPARAM\n"
        "    return a, b, c, d\n"
    )
    path, findings = _lint_fixture(tmp_path, src)
    raw = [f for f in findings if f.code == "knob-unregistered"]
    assert len(raw) == 4
    by_line = {f.lineno: f for f in raw}
    assert set(by_line) == {
        _marker_line(src, m)
        for m in ("MARK-GET", "MARK-SUBSCRIPT", "MARK-IN", "MARK-ENVPARAM")
    }
    f = by_line[_marker_line(src, "MARK-GET")]
    assert f.severity == "error"
    assert f.source_file == path
    # a registered name gets pointed at its typed accessor
    assert "get_float('TPUFLOW_HANG_FLOOR_S')" in f.message


def test_knob_unregistered_indirected_constant(tmp_path):
    # module-level NAME = "TPUFLOW_..." constants are resolved
    src = (
        "import os\n"
        "DETECT_ENV = 'TPUFLOW_HANG_DETECT'\n"
        "flag = os.environ.get(DETECT_ENV, '1')  # MARK-INDIRECT\n"
    )
    _path, findings = _lint_fixture(tmp_path, src)
    raw = [f for f in findings if f.code == "knob-unregistered"]
    assert [f.lineno for f in raw] == [_marker_line(src, "MARK-INDIRECT")]
    assert "TPUFLOW_HANG_DETECT" in raw[0].message


def test_knob_unknown_with_did_you_mean(tmp_path):
    src = (
        "from metaflow_tpu import knobs\n"
        "x = knobs.get_float('TPUFLOW_HANG_FLOR_S')  # MARK-TYPO\n"
    )
    path, findings = _lint_fixture(tmp_path, src)
    unknown = [f for f in findings if f.code == "knob-unknown"]
    assert len(unknown) == 1
    assert unknown[0].severity == "error"
    assert unknown[0].source_file == path
    assert unknown[0].lineno == _marker_line(src, "MARK-TYPO")
    assert "did you mean TPUFLOW_HANG_FLOOR_S?" in unknown[0].message


def test_knob_inconsistent_default(tmp_path):
    # registry default for TPUFLOW_HANG_FLOOR_S is 60.0; a call site
    # claiming 120.0 means two subsystems disagree on the unset value
    src = (
        "from metaflow_tpu import knobs\n"
        "ok = knobs.get_float('TPUFLOW_HANG_FLOOR_S', fallback=60.0)\n"
        "bad = knobs.get_float('TPUFLOW_HANG_FLOOR_S', "
        "fallback=120.0)  # MARK-DRIFT\n"
    )
    path, findings = _lint_fixture(tmp_path, src)
    drift = [f for f in findings if f.code == "knob-inconsistent-default"]
    assert len(drift) == 1
    assert drift[0].severity == "error"
    assert drift[0].source_file == path
    assert drift[0].lineno == _marker_line(src, "MARK-DRIFT")
    assert "registry default" in drift[0].message


def test_knob_inconsistent_default_numeric_canonicalization(tmp_path):
    # '60', 60 and 60.0 are the SAME default for a float knob; a bare
    # accessor call (registry default) is not a drift site at all
    src = (
        "from metaflow_tpu import knobs\n"
        "a = knobs.get_float('TPUFLOW_HANG_FLOOR_S')\n"
        "b = knobs.get_float('TPUFLOW_HANG_FLOOR_S', fallback=60)\n"
    )
    _path, findings = _lint_fixture(tmp_path, src)
    assert [f for f in findings if f.code == "knob-inconsistent-default"] \
        == []


def test_knob_undocumented(tmp_path):
    with open(DOCS_PATH) as handle:
        docs_text = handle.read()
    gutted = docs_text.replace("TPUFLOW_HANG_FLOOR_S", "TPUFLOW_GONE")
    _path, findings = _lint_fixture(tmp_path, "x = 1\n", docs_text=gutted)
    undoc = [f for f in findings if f.code == "knob-undocumented"]
    assert len(undoc) == 1
    assert undoc[0].severity == "warning"
    assert undoc[0].source_file == "knobs.py"
    assert "TPUFLOW_HANG_FLOOR_S" in undoc[0].message
    # the checked-in docs are complete
    _path, findings = _lint_fixture(tmp_path, "x = 1\n", docs_text=docs_text)
    assert [f for f in findings if f.code == "knob-undocumented"] == []


# ---------------------------------------------------------------------------
# seeded violations: deadline ordering
# ---------------------------------------------------------------------------


def test_deadline_order_env_violation():
    env = {"TPUFLOW_HANG_FLOOR_S": "10"}
    findings = [f for f in deadline_order(env=env)
                if f.code == "deadline-order"]
    # both MPMD timeouts (default 60) now exceed the hang floor
    assert len(findings) == 2
    assert all(f.severity == "warning" for f in findings)
    assert all(f.source_file == "<environment>" for f in findings)
    msgs = " ".join(f.message for f in findings)
    assert "TPUFLOW_MPMD_RECV_TIMEOUT_S=60" in msgs
    assert "TPUFLOW_MPMD_SEND_TIMEOUT_S=60" in msgs
    assert "TPUFLOW_HANG_FLOOR_S=10" in msgs


def test_deadline_order_registry_defaults_hold():
    assert deadline_order() == []
    assert knobs.validate_defaults() == []


def test_deadline_order_inheritance():
    # an explicit send timeout inherits nothing; an unset one follows
    # the recv timeout it defaults to
    bad = knobs.validate_env({"TPUFLOW_MPMD_RECV_TIMEOUT_S": "30",
                              "TPUFLOW_HANG_FLOOR_S": "45"})
    assert bad == []
    bad = knobs.validate_env({"TPUFLOW_MPMD_RECV_TIMEOUT_S": "50",
                              "TPUFLOW_HANG_FLOOR_S": "45"})
    assert [v.lo for v in bad] == ["TPUFLOW_MPMD_RECV_TIMEOUT_S",
                                   "TPUFLOW_MPMD_SEND_TIMEOUT_S"]


def test_ordering_edges_reference_registered_knobs():
    for edge in knobs.ORDERING:
        assert edge.lo in knobs.KNOBS, edge.lo
        assert edge.hi in knobs.KNOBS, edge.hi
        assert edge.reason


# ---------------------------------------------------------------------------
# seeded violations: telemetry schema drift (both directions)
# ---------------------------------------------------------------------------

_TELEMETRY_SCHEMA_FIXTURE = (
    "FIXTURE_EVENT_DATA_SCHEMAS = {\n"
    "    'pinned.dead': {'type': 'object'},  # MARK-DEAD-PIN\n"
    "    'pinned.live': {'type': 'object'},\n"
    "}\n"
    "FIXTURE_METRIC_NAMES = {'pinned.metric': 'gauge'}\n"
    "EXTRA_PINNED_TELEMETRY_NAMES = ('pinned.extra',)\n"
    "DYNAMIC_EMIT_PREFIXES = ('dyn.',)\n"
    "DYNAMIC_EMIT_SUFFIXES = ('.compile',)\n"
)

_TELEMETRY_LIB_FIXTURE = (
    "def run(record, step):\n"
    "    record.event('pinned.live', {})\n"
    "    record.gauge('pinned.metric', 1.0)\n"
    "    record.event('bogus.event', {})  # MARK-UNPINNED\n"
    "    record.timer('dyn.anything', 5.0)\n"
    "    record.timer('%s.compile' % step, 5.0)\n"
    "    name = 'pinned.extra'\n"
    "    record.event(name, {})\n"
)


def _telemetry_fixture(tmp_path):
    schema = tmp_path / "schema_fixture.py"
    schema.write_text(_TELEMETRY_SCHEMA_FIXTURE)
    lib = tmp_path / "lib_fixture.py"
    lib.write_text(_TELEMETRY_LIB_FIXTURE)
    _reads, _accessors, emits = scan_paths([str(lib)])
    return str(schema), str(lib), \
        telemetry_drift(emits, str(schema), [str(lib)])


def test_telemetry_unpinned_event(tmp_path):
    _schema, lib, findings = _telemetry_fixture(tmp_path)
    unpinned = [f for f in findings if f.code == "telemetry-unpinned-event"]
    assert len(unpinned) == 1
    assert unpinned[0].severity == "error"
    assert unpinned[0].source_file == lib
    assert unpinned[0].lineno == _marker_line(_TELEMETRY_LIB_FIXTURE,
                                              "MARK-UNPINNED")
    assert "'bogus.event'" in unpinned[0].message


def test_telemetry_dead_schema(tmp_path):
    schema, _lib, findings = _telemetry_fixture(tmp_path)
    dead = [f for f in findings if f.code == "telemetry-dead-schema"]
    assert len(dead) == 1
    assert dead[0].severity == "warning"
    assert dead[0].source_file == schema
    assert dead[0].lineno == _marker_line(_TELEMETRY_SCHEMA_FIXTURE,
                                          "MARK-DEAD-PIN")
    assert "'pinned.dead'" in dead[0].message
    # a pin whose name appears as a non-emit literal (names picked
    # before the emit call) stays live
    assert not any("pinned.extra" in f.message for f in findings)


def test_load_pins_reads_the_real_schema_module():
    pins, prefixes, suffixes = load_pins(SCHEMA_PATH)
    # spot-check families from different pin tables
    for name in ("task.start", "sanitize.desync", "task.queue_seconds",
                 "slo.breach", "goodput.interval"):
        assert name in pins, name
    assert ".compile" in suffixes
    assert isinstance(prefixes, tuple)


# ---------------------------------------------------------------------------
# library self-scan: the migration-completeness gate
# ---------------------------------------------------------------------------


def test_library_contracts_sweep_is_clean():
    report = analyze_library([LIBRARY], schema_path=SCHEMA_PATH,
                             docs_path=DOCS_PATH)
    assert report.analyses == ["contracts"]
    assert [f.render() for f in report.errors] == []
    assert [f.render() for f in report.warnings] == []


def test_no_raw_tpuflow_reads_outside_registry():
    """Zero raw TPUFLOW_* env reads anywhere in the library: every read
    goes through knobs.py (which scan_paths itself exempts)."""
    reads, accessors, _emits = scan_paths([LIBRARY])
    assert [(s.path, s.lineno, s.name) for s in reads] == []
    # and every accessor call names a registered knob
    unknown = [(s.path, s.lineno, s.name) for s in accessors
               if s.name not in knobs.KNOBS]
    assert unknown == []


def test_registry_entries_are_complete():
    for name, knob in sorted(knobs.KNOBS.items()):
        assert name.startswith("TPUFLOW_"), name
        assert knob.ktype in ("str", "int", "float", "bool", "path"), name
        assert knob.subsystem, name
        assert knob.doc, name


def test_the_decode_chunk_is_a_constant_and_no_knob():
    """`TPUFLOW_DECODE_CHUNK` set both the chunk loop's chunk and where an
    engine switched reads, at import; no cell and no chip run ever passed
    another value. It is a constant of inference/decode.py."""
    from metaflow_tpu.inference import decode

    assert "TPUFLOW_DECODE_CHUNK" not in knobs.KNOBS
    assert decode.DECODE_CHUNK == 256
    assert len(knobs.KNOBS) <= 155   # and no knob came in its place


def test_the_cache_module_imports_nothing_of_the_block_or_the_engines():
    """inference/cache.py is what inference/decode.py and serving/ read
    the cache's format from: the arrows point one way."""
    import ast

    path = os.path.join(LIBRARY, "inference", "cache.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            module = "." * node.level + (node.module or "")
            imported += ["%s.%s" % (module, alias.name)
                         for alias in node.names]
    assert imported, path
    for name in imported:
        assert "decode" not in name.split(".") \
            and "serving" not in name.split("."), name


# ---------------------------------------------------------------------------
# regression: defaults that used to drift between call sites
# ---------------------------------------------------------------------------


def test_registry_defaults_match_module_constants():
    """The constants the pre-registry call sites used to duplicate now
    have exactly one home; these pin the registry to the module-level
    reference constants that remain (kept for tests/back-compat)."""
    from metaflow_tpu import progress
    from metaflow_tpu.plugins.tpu import preemption

    assert knobs.KNOBS["TPUFLOW_HANG_FLOOR_S"].default \
        == progress.DEFAULT_FLOOR_S
    assert knobs.KNOBS["TPUFLOW_HANG_DEADLINE_MULT"].default \
        == progress.DEFAULT_MULT
    assert knobs.KNOBS["TPUFLOW_HANG_COMPILE_GRACE_S"].default \
        == progress.DEFAULT_COMPILE_GRACE_S
    assert knobs.KNOBS["TPUFLOW_SPOT_METADATA_URL"].default \
        == preemption.DEFAULT_METADATA_URL
    # TPUFLOW_HANG_DUMP_SIGNAL is a signal NUMBER (0 = use SIGQUIT),
    # not a flag — it was registered as bool once
    assert knobs.KNOBS["TPUFLOW_HANG_DUMP_SIGNAL"].ktype == "int"
    assert knobs.KNOBS["TPUFLOW_HANG_DUMP_SIGNAL"].default == 0


def test_accessor_semantics():
    env = {"TPUFLOW_SANITIZE_WINDOW": "not-a-number",
           "TPUFLOW_HANG_FLOOR_S": "",
           "TPUFLOW_DEBUG": "off"}
    # malformed numeric and empty string both fall back to the registry
    assert knobs.get_int("TPUFLOW_SANITIZE_WINDOW", env=env) == 512
    assert knobs.get_float("TPUFLOW_HANG_FLOOR_S", env=env) == 60.0
    assert knobs.get_bool("TPUFLOW_DEBUG", env=env) is False
    assert knobs.get_bool("TPUFLOW_DEBUG", env={"TPUFLOW_DEBUG": "1"}) \
        is True
    # get_raw: raw string when set non-empty, None otherwise (empty
    # string means "unset" everywhere in the library)
    assert knobs.get_raw("TPUFLOW_HANG_FLOOR_S", env=env) is None
    assert knobs.get_raw("TPUFLOW_DEBUG", env=env) == "off"
    assert knobs.get_raw("TPUFLOW_SANITIZE", env=env) is None
    assert knobs.is_set("TPUFLOW_DEBUG", env=env)
    assert not knobs.is_set("TPUFLOW_SANITIZE", env=env)
    # explicit fallback beats the registry default when unset (via a
    # variable: a literal here would trip the drift lint on this file)
    fallback = 90.0
    assert knobs.get_float("TPUFLOW_HANG_FLOOR_S", env={},
                           fallback=fallback) == 90.0


# ---------------------------------------------------------------------------
# wiring: check --deep, the pre-run gate, analyze_all.sh
# ---------------------------------------------------------------------------


class _GateFlow(FlowSpec):
    @step
    def start(self):
        self.next(self.end)

    @step
    def end(self):
        pass


def test_analyze_flow_carries_contracts():
    report = analyze_flow(_GateFlow)
    assert "contracts" in report.analyses
    assert report.ok, [f.render() for f in report.errors]


def test_pre_run_gate_warns_by_default(monkeypatch):
    monkeypatch.setenv("TPUFLOW_HANG_FLOOR_S", "10")
    monkeypatch.delenv("TPUFLOW_STRICT_CHECK", raising=False)
    lines = []
    report = pre_run_gate(_GateFlow, FlowGraph(_GateFlow), lines.append)
    assert report is not None and not report.errors
    echoed = "\n".join(lines)
    assert "deadline-order" in echoed
    assert "TPUFLOW_HANG_FLOOR_S=10" in echoed


def test_pre_run_gate_strict_rejects_misordered_deadlines(monkeypatch):
    monkeypatch.setenv("TPUFLOW_HANG_FLOOR_S", "10")
    monkeypatch.setenv("TPUFLOW_STRICT_CHECK", "1")
    with pytest.raises(AnalysisError) as excinfo:
        pre_run_gate(_GateFlow, FlowGraph(_GateFlow), lambda _msg: None)
    assert "TPUFLOW_MPMD_RECV_TIMEOUT_S" in str(excinfo.value)


def test_analyze_contracts_flags_flow_env_typos(tmp_path):
    flow_file = tmp_path / "typo_flow.py"
    flow_file.write_text(
        "import os\n"
        "threshold = os.environ.get('TPUFLOW_HANG_FLOR_S', '60')\n"
    )
    report = analyze_contracts(str(flow_file), env={})
    codes = [f.code for f in report.findings]
    assert codes == ["knob-unregistered"]
    assert "did you mean TPUFLOW_HANG_FLOOR_S?" in \
        report.findings[0].message


def test_contracts_cli_exit_codes(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    clean = subprocess.run(
        [sys.executable, "-m", "metaflow_tpu.analysis.contracts", LIBRARY,
         "--schema", SCHEMA_PATH, "--docs", DOCS_PATH, "--json"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    report = json.loads(clean.stdout)
    assert report["ok"] is True
    assert report["analyses"] == ["contracts"]
    schema_validate.validate_check_report(report)

    dirty = tmp_path / "dirty.py"
    dirty.write_text("import os\nx = os.environ['TPUFLOW_NOT_A_KNOB']\n")
    bad = subprocess.run(
        [sys.executable, "-m", "metaflow_tpu.analysis.contracts",
         str(dirty)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "knob-unregistered" in bad.stdout


def test_check_deep_json_carries_contracts():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tests", "flows", "branch_flow.py"),
         "check", "--deep", "--json"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    report = json.loads(out.stdout)
    schema_validate.validate_check_report(report)
    assert "contracts" in report["analyses"]


# ---------------------------------------------------------------------------
# registry surfaces: docs/knobs.md + the knobs CLI + pinned codes
# ---------------------------------------------------------------------------


def test_knobs_markdown_matches_checked_in_docs():
    """docs/knobs.md is generated — `python -m metaflow_tpu knobs
    --markdown` must reproduce it byte-for-byte."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "metaflow_tpu", "knobs", "--markdown"],
        capture_output=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr.decode()
    with open(DOCS_PATH, "rb") as handle:
        checked_in = handle.read()
    assert out.stdout == checked_in, (
        "docs/knobs.md drifted from the registry — regenerate with "
        "`python -m metaflow_tpu knobs --markdown > docs/knobs.md`")


def test_knobs_markdown_covers_every_knob():
    with open(DOCS_PATH) as handle:
        docs_text = handle.read()
    for name in knobs.KNOBS:
        assert "`%s`" % name in docs_text, name


def test_knobs_check_env_rejects_misordered_deadlines():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               TPUFLOW_HANG_FLOOR_S="10")
    out = subprocess.run(
        [sys.executable, "-m", "metaflow_tpu", "knobs", "--check-env"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "ordering violation" in out.stdout
    assert "TPUFLOW_MPMD_RECV_TIMEOUT_S" in out.stdout

    env.pop("TPUFLOW_HANG_FLOOR_S")
    ok = subprocess.run(
        [sys.executable, "-m", "metaflow_tpu", "knobs", "--check-env"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "deadline ordering: ok" in ok.stdout


def test_knobs_json_view():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "metaflow_tpu", "knobs", "--json"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    dump = json.loads(out.stdout)
    names = {k["name"] for k in dump["knobs"]}
    assert names == set(knobs.KNOBS)
    assert len(dump["ordering"]) == len(knobs.ORDERING)


def test_contract_finding_codes_pinned():
    assert schema_validate.CONTRACT_FINDING_CODES == CONTRACT_FINDING_CODES


def test_scan_source_tolerates_broken_files():
    assert scan_source("broken.py", "def oops(:\n") is None
