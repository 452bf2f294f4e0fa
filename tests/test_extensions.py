"""Extension mechanism: metaflow_tpu_extensions.* namespace-package discovery.

Reference behavior: metaflow/extension_support/plugins.py:15,140 — an
installed extension package adds/overrides plugins in every category without
touching core. Here we materialize an extension on disk, point sys.path at
it, and check each category merges; then run a real flow in a subprocess
with the extension on PYTHONPATH and `--with` the extension's decorator.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXT_PLUGINS = textwrap.dedent(
    """
    import click
    from metaflow_tpu.decorators import StepDecorator, FlowDecorator
    from metaflow_tpu.datastore.storage import LocalStorage


    class TraceMeDecorator(StepDecorator):
        name = "traceme"
        defaults = {"tag": "ext"}

        def task_post_step(self, step_name, flow, graph, retry_count,
                           max_user_code_retries):
            seen = list(getattr(flow, "ext_trace", []))
            seen.append("%s:%s" % (step_name, self.attributes["tag"]))
            flow.ext_trace = seen


    class ShadowStorage(LocalStorage):
        TYPE = "shadow"


    @click.command(help="extension-added command")
    def ext_hello():
        click.echo("hello-from-extension")


    from metaflow_tpu.datastore.serializers import ArtifactSerializer


    class Rot13Serializer(ArtifactSerializer):
        # a custom artifact format contributed by the extension
        type_tag = "rot13"
        priority = 5  # ahead of every built-in

        def can_serialize(self, obj):
            return isinstance(obj, str) and obj.startswith("rot13:")

        def serialize(self, obj):
            import codecs
            return codecs.encode(obj, "rot13").encode("utf-8")

        def deserialize(self, payload):
            import codecs
            return codecs.decode(payload.decode("utf-8"), "rot13")


    STEP_DECORATORS = [TraceMeDecorator]
    STORAGE_BACKENDS = {"shadow": ShadowStorage}
    CLI_COMMANDS = [ext_hello]
    SERIALIZERS = [Rot13Serializer()]


    def register(api):
        register.called = True
    """
)

FLOW = textwrap.dedent(
    """
    from metaflow_tpu import FlowSpec, step

    class ExtFlow(FlowSpec):
        @step
        def start(self):
            self.next(self.end)

        @step
        def end(self):
            print("TRACE=%s" % ",".join(getattr(self, "ext_trace", [])))

    if __name__ == "__main__":
        ExtFlow()
    """
)


@pytest.fixture
def ext_dir(tmp_path):
    pkg = tmp_path / "extroot" / "metaflow_tpu_extensions" / "myext"
    pkg.mkdir(parents=True)
    # PEP-420: no __init__.py at the metaflow_tpu_extensions root
    (pkg / "__init__.py").write_text("")
    (pkg / "plugins.py").write_text(EXT_PLUGINS)
    return str(tmp_path / "extroot")


def test_load_extensions_merges_all_categories(ext_dir):
    from metaflow_tpu import extension_support as ext
    from metaflow_tpu import plugins
    from metaflow_tpu.datastore.storage import STORAGE_BACKENDS

    sys.path.insert(0, ext_dir)
    try:
        loaded = ext.load_extensions(force=True)
        assert "metaflow_tpu_extensions.myext" in loaded
        assert "traceme" in plugins.STEP_DECORATORS
        assert "shadow" in STORAGE_BACKENDS
        assert any(
            getattr(c, "name", "") == "ext-hello" for c in ext.CLI_COMMANDS
        )
        # the extension's serializer takes priority for its objects and
        # round-trips through the tag registry
        from metaflow_tpu.datastore import serializers

        payload, tag = serializers.serialize("rot13:secret")
        assert tag == "rot13"
        assert serializers.deserialize(payload, tag) == "rot13:secret"
        # everything else still routes to the built-ins
        _, tag = serializers.serialize("plain string")
        assert tag == serializers.TYPE_PICKLE
        # importable like a core decorator
        import metaflow_tpu

        assert callable(getattr(metaflow_tpu, "traceme"))
    finally:
        sys.path.remove(ext_dir)
        plugins.STEP_DECORATORS.pop("traceme", None)
        STORAGE_BACKENDS.pop("shadow", None)
        ext.CLI_COMMANDS.clear()
        from metaflow_tpu.datastore import serializers as _s

        rot = _s._BY_TAG.pop("rot13", None)
        if rot is not None:
            _s._SERIALIZERS.remove(rot)


def test_broken_extension_is_skipped_not_fatal(tmp_path):
    from metaflow_tpu import extension_support as ext

    pkg = tmp_path / "extroot" / "metaflow_tpu_extensions" / "broken"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("raise RuntimeError('boom')")
    sys.path.insert(0, str(tmp_path / "extroot"))
    try:
        ext.load_extensions(force=True)  # must not raise
        assert "metaflow_tpu_extensions.broken" in ext.failed_extensions()
    finally:
        sys.path.remove(str(tmp_path / "extroot"))


def _ext_pythonpath(ext_dir):
    # run_flow builds the base env; we only extend PYTHONPATH with the
    # extension root (keeping repo + inherited entries)
    inherited = [
        p
        for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
        if p
    ]
    return os.pathsep.join([REPO, ext_dir] + inherited)


def test_extension_decorator_runs_in_flow(ext_dir, tmp_path, run_flow):
    flow_file = tmp_path / "ext_flow.py"
    flow_file.write_text(FLOW)
    out = run_flow(
        str(flow_file),
        "--with",
        "traceme:tag=X",
        "run",
        env_extra={"PYTHONPATH": _ext_pythonpath(ext_dir)},
    )
    assert "TRACE=start:X" in out.stdout + out.stderr


def test_extension_cli_command(ext_dir, tmp_path, run_flow):
    flow_file = tmp_path / "ext_flow.py"
    flow_file.write_text(FLOW)
    out = run_flow(
        str(flow_file),
        "ext-hello",
        env_extra={"PYTHONPATH": _ext_pythonpath(ext_dir)},
    )
    assert "hello-from-extension" in out.stdout
