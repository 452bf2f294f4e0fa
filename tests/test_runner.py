"""Programmatic API: click reflection, async supervision, NBDeploy.

Reference behavior: metaflow/runner/{metaflow_runner,click_api,
subprocess_manager,nbdeploy}.py — Runner kwargs mirror the CLI surface,
unknown kwargs fail fast, async runs stream logs and die cleanly on
timeout/kill.
"""

import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLOWS = os.path.join(REPO, "tests", "flows")


@pytest.fixture
def runner_env(tpuflow_root, monkeypatch):
    monkeypatch.setenv("TPUFLOW_DATASTORE_SYSROOT_LOCAL", tpuflow_root)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_PLATFORM_NAME", "cpu")
    # CPU-only subprocesses, same reasoning as conftest.run_flow
    pythonpath = os.pathsep.join(
        [REPO]
        + [
            p
            for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
            if p
        ]
    )
    monkeypatch.setenv("PYTHONPATH", pythonpath)
    return tpuflow_root


class TestReflection:
    def test_commands_discovered(self, runner_env):
        from metaflow_tpu import Runner

        with Runner(os.path.join(FLOWS, "linear_flow.py")) as r:
            names = r.command_names()
        assert "run" in names and "resume" in names and "show" in names

    def test_flow_parameters_are_run_options(self, runner_env):
        from metaflow_tpu.runner.click_api import FlowCLIReflection

        api = FlowCLIReflection(os.path.join(FLOWS, "linear_flow.py"))
        spec = api.command("run")
        assert "alpha" in spec.params  # the flow's Parameter
        assert "max_workers" in spec.params
        assert spec.params["tags"].multiple

    def test_unknown_kwarg_fails_fast_with_choices(self, runner_env):
        from metaflow_tpu import Runner
        from metaflow_tpu.runner.click_api import UnknownCLIOption

        with Runner(os.path.join(FLOWS, "linear_flow.py")) as r:
            with pytest.raises(UnknownCLIOption) as err:
                r.run(alhpa=0.5)  # typo
        assert "alhpa" in str(err.value)
        assert "alpha" in str(err.value)  # valid options listed

    def test_nested_command_reflection(self, runner_env):
        from metaflow_tpu.runner.click_api import FlowCLIReflection

        api = FlowCLIReflection(os.path.join(FLOWS, "linear_flow.py"))
        assert api.command("tag add") is not None
        assert api.command("no-such-cmd") is None


class TestRunnerRun:
    def test_run_with_parameter(self, runner_env):
        from metaflow_tpu import Runner

        with Runner(os.path.join(FLOWS, "linear_flow.py")) as r:
            result = r.run(alpha=0.25)
        assert result.status == "successful", result.stderr
        assert result.run is not None
        assert result.run.data.scaled == 2.5

    def test_top_level_with_decospec(self, runner_env):
        from metaflow_tpu import Runner

        with Runner(
            os.path.join(FLOWS, "linear_flow.py"),
            decospecs=["retry:times=1"],
        ) as r:
            result = r.run(alpha=0.1)
        assert result.status == "successful", result.stderr

    def test_run_timeout_kills_process_group(self, runner_env, tmp_path):
        from metaflow_tpu import Runner
        from metaflow_tpu.exception import TpuFlowException

        slow = tmp_path / "slow_flow.py"
        slow.write_text(
            "from metaflow_tpu import FlowSpec, step\n"
            "import time\n"
            "class SlowFlow(FlowSpec):\n"
            "    @step\n"
            "    def start(self):\n"
            "        time.sleep(600)\n"
            "        self.next(self.end)\n"
            "    @step\n"
            "    def end(self):\n"
            "        pass\n"
            "if __name__ == '__main__':\n"
            "    SlowFlow()\n"
        )
        with Runner(str(slow)) as r:
            t0 = time.time()
            with pytest.raises(TpuFlowException, match="timed out"):
                r.run(timeout=8)
        assert time.time() - t0 < 60


class TestAsyncRun:
    def test_async_run_streams_and_waits(self, runner_env):
        from metaflow_tpu import Runner

        with Runner(os.path.join(FLOWS, "linear_flow.py")) as r:
            ar = r.async_run(alpha=0.5)
            assert ar.run_id  # becomes available while running
            lines = list(ar.stream_log("stdout"))
            result = ar.wait(timeout=120)
        assert result.status == "successful", result.stderr
        assert any("final x" in line for line in lines)

    def test_terminate(self, runner_env, tmp_path):
        from metaflow_tpu import Runner

        slow = tmp_path / "slow2_flow.py"
        slow.write_text(
            "from metaflow_tpu import FlowSpec, step\n"
            "import time\n"
            "class Slow2Flow(FlowSpec):\n"
            "    @step\n"
            "    def start(self):\n"
            "        time.sleep(600)\n"
            "        self.next(self.end)\n"
            "    @step\n"
            "    def end(self):\n"
            "        pass\n"
            "if __name__ == '__main__':\n"
            "    Slow2Flow()\n"
        )
        with Runner(str(slow)) as r:
            ar = r.async_run()
            assert ar.run_id
            ar.terminate()
            deadline = time.time() + 30
            while ar._cm.running and time.time() < deadline:
                time.sleep(0.2)
            assert not ar._cm.running


class TestRunnerContracts:
    def test_namespace_alias_resolves_renamed_param(self, runner_env):
        # click declares ('--namespace', 'user_namespace'); both kwarg
        # spellings must work
        from metaflow_tpu.runner.click_api import FlowCLIReflection

        api = FlowCLIReflection(os.path.join(FLOWS, "linear_flow.py"))
        argv = api.build_command_argv("run", {"namespace": "prod"})
        assert argv == ["run", "--namespace", "prod"]
        argv = api.build_command_argv("run", {"user_namespace": "prod"})
        assert argv == ["run", "--namespace", "prod"]

    def test_async_run_survives_runner_exit(self, runner_env):
        from metaflow_tpu import Runner

        with Runner(os.path.join(FLOWS, "linear_flow.py")) as r:
            ar = r.async_run(alpha=0.5)
        # the with-block has exited; the backgrounded run must complete
        result = ar.wait(timeout=120)
        assert result.status == "successful", result.stderr

    def test_async_wait_timeout_raises_and_kills(self, runner_env, tmp_path):
        from metaflow_tpu import Runner
        from metaflow_tpu.exception import TpuFlowException

        slow = tmp_path / "slow3_flow.py"
        slow.write_text(
            "from metaflow_tpu import FlowSpec, step\n"
            "import time\n"
            "class Slow3Flow(FlowSpec):\n"
            "    @step\n"
            "    def start(self):\n"
            "        time.sleep(600)\n"
            "        self.next(self.end)\n"
            "    @step\n"
            "    def end(self):\n"
            "        pass\n"
            "if __name__ == '__main__':\n"
            "    Slow3Flow()\n"
        )
        with Runner(str(slow)) as r:
            ar = r.async_run()
            assert ar.run_id
            with pytest.raises(TpuFlowException, match="timed out"):
                ar.wait(timeout=5)
            assert not ar._cm.running


class TestResume:
    def test_programmatic_resume(self, runner_env, tmp_path):
        from metaflow_tpu import Runner

        flaky = tmp_path / "flaky_flow.py"
        flaky.write_text(
            "import os\n"
            "from metaflow_tpu import FlowSpec, step\n"
            "class FlakyRunnerFlow(FlowSpec):\n"
            "    @step\n"
            "    def start(self):\n"
            "        self.x = 41\n"
            "        self.next(self.middle)\n"
            "    @step\n"
            "    def middle(self):\n"
            "        if os.environ.get('MAKE_IT_FAIL'):\n"
            "            raise RuntimeError('boom')\n"
            "        self.y = self.x + 1\n"
            "        self.next(self.end)\n"
            "    @step\n"
            "    def end(self):\n"
            "        print('y =', self.y)\n"
            "if __name__ == '__main__':\n"
            "    FlakyRunnerFlow()\n"
        )
        with Runner(str(flaky), env={"MAKE_IT_FAIL": "1"}) as r:
            first = r.run()
            assert first.status == "failed"
        with Runner(str(flaky)) as r:
            resumed = r.resume()
            assert resumed.status == "successful", resumed.stderr
            assert resumed.run.data.y == 42


class TestNBDeploy:
    def test_nbdeployer_compiles_argo(self, runner_env):
        import textwrap

        # NBDeployer needs inspect.getsource: define the class in a real file
        import importlib.util
        import tempfile

        src = textwrap.dedent(
            """
            from metaflow_tpu import FlowSpec, step

            class NBDeployFlow(FlowSpec):
                @step
                def start(self):
                    self.next(self.end)

                @step
                def end(self):
                    pass
            """
        )
        d = tempfile.mkdtemp()
        path = os.path.join(d, "nbflow_mod.py")
        with open(path, "w") as f:
            f.write(src)
        spec = importlib.util.spec_from_file_location("nbflow_mod", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["nbflow_mod"] = mod
        spec.loader.exec_module(mod)

        from metaflow_tpu import NBDeployer

        dep = NBDeployer(mod.NBDeployFlow)
        deployed = dep.argo_workflows(
            datastore_root="/srv/shared/tpuflow"
        ).create()
        assert "NBDeployFlow".lower() in (deployed.name or "").lower() or \
            deployed.manifests
        assert "WorkflowTemplate" in deployed.manifests
        dep.cleanup()


class TestDeployerTrigger:
    """trigger(): Workflow-from-template submission through kubectl
    (faked here — the gcloud-launcher test pattern)."""

    @pytest.fixture
    def fake_kubectl(self, tmp_path, monkeypatch):
        log = tmp_path / "kubectl.log"
        script = tmp_path / "kubectl"
        script.write_text(
            "#!/bin/bash\n"
            "echo \"$@\" >> %s\n"
            "stdin=$(cat)\n"
            "echo \"$stdin\" >> %s\n"
            "if [ \"$1\" = create ]; then\n"
            "  echo '{\"metadata\": {\"name\": \"linearflow-abc12\"}}'\n"
            "elif [ \"$1\" = get ]; then\n"
            "  echo '{\"status\": {\"phase\": \"Succeeded\"}}'\n"
            "else\n"
            "  echo applied\n"
            "fi\n" % (log, log)
        )
        script.chmod(0o755)
        monkeypatch.setenv("TPUFLOW_KUBECTL", str(script))
        return log

    def test_create_apply_trigger_status(self, runner_env, fake_kubectl):
        from metaflow_tpu.runner import Deployer

        deployed = Deployer(
            os.path.join(FLOWS, "linear_flow.py")
        ).argo_workflows(datastore_root="/srv/shared/tpuflow").create()
        assert "WorkflowTemplate" in deployed.manifests

        deployed.apply()
        run = deployed.trigger(alpha=2.5)
        assert run.workflow_name == "linearflow-abc12"
        assert run.run_id == "argo-linearflow-abc12"
        assert run.status() == "Succeeded"

        logged = fake_kubectl.read_text()
        assert "workflowTemplateRef" in logged
        assert '"alpha"' in logged and "2.5" in logged

    def test_trigger_manifest_without_kubectl(self, runner_env):
        from metaflow_tpu.runner import Deployer

        deployed = Deployer(
            os.path.join(FLOWS, "linear_flow.py")
        ).argo_workflows(datastore_root="/srv/shared/tpuflow").create()
        m = deployed.trigger_manifest(alpha=1.5)
        assert m["kind"] == "Workflow"
        assert m["spec"]["workflowTemplateRef"]["name"] == deployed.name
        assert m["spec"]["arguments"]["parameters"] == [
            {"name": "alpha", "value": "1.5"}
        ]
