"""Event publishing + triggering end-to-end (VERDICT round-2 item #3).

The local runtime publishes run-finished.<flow> to the JSONL bus at run
completion; LocalTriggerListener plays the Argo Events sensor locally,
launching @trigger/@trigger_on_finish subscribers with the consumed
events surfaced as `current.trigger`.

Reference behavior: metaflow/plugins/argo/argo_events.py (publish:90) +
events.py Trigger, invoked from the Argo workflow's final templates.
"""

import json
import os
import subprocess
import sys

import pytest

FLOWS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "flows")


def _env(root):
    env = dict(os.environ)
    env["TPUFLOW_DATASTORE_SYSROOT_LOCAL"] = root
    env["TPUFLOW_CLIENT_CACHE"] = os.path.join(root, "blobcache")
    inherited = [
        p for p in env.get("PYTHONPATH", "").split(os.pathsep)
        if p
    ]
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + inherited
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    return env


def _run(script, root, *args):
    proc = subprocess.run(
        [sys.executable, os.path.join(FLOWS, script), "run"] + list(args),
        env=_env(root), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    return proc


class TestLocalEventBus:
    def test_run_completion_publishes_run_finished(self, tpuflow_root):
        from metaflow_tpu.events import list_events

        _run("linear_flow.py", tpuflow_root)
        events = list_events()
        names = [e["name"] for e in events]
        assert "run-finished.LinearFlow" in names
        record = events[names.index("run-finished.LinearFlow")]
        assert record["payload"]["flow"] == "LinearFlow"
        assert record["payload"]["status"] == "successful"
        assert record["payload"]["run_id"]

    def test_failed_run_publishes_nothing(self, tpuflow_root):
        from metaflow_tpu.events import list_events

        env = _env(tpuflow_root)
        env["MAKE_IT_FAIL"] = "1"
        proc = subprocess.run(
            [sys.executable, os.path.join(FLOWS, "exit_hook_flow.py"),
             "run"],
            env=env, capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode != 0
        assert list_events() == []

    def test_publish_event_api(self, tpuflow_root):
        from metaflow_tpu.events import ArgoEvent, list_events

        ArgoEvent("data_ready").add_to_payload("path", "gs://b/x").publish()
        (record,) = list_events()
        assert record["name"] == "data_ready"
        assert record["payload"]["path"] == "gs://b/x"


class TestTriggerListener:
    def test_trigger_on_finish_chain(self, tpuflow_root):
        """Flow A finishing triggers flow B off the bus; B sees the event
        through current.trigger."""
        from metaflow_tpu.events import LocalTriggerListener

        listener = LocalTriggerListener(env=_env(tpuflow_root))
        names = listener.register(os.path.join(FLOWS, "triggered_flow.py"))
        assert names == ["run-finished.LinearFlow"]

        # nothing on the bus yet: no launches
        assert listener.poll_once() == []

        _run("linear_flow.py", tpuflow_root)
        launched = listener.poll_once()
        assert len(launched) == 1
        script, rc, matched = launched[0]
        assert rc == 0
        assert [e["name"] for e in matched] == ["run-finished.LinearFlow"]

        from metaflow_tpu.client import Flow, namespace

        namespace(None)
        run = list(Flow("TriggeredFlow"))[0]
        assert run.successful
        task = run["start"].task
        assert task["event_name"].data == "run-finished.LinearFlow"
        # the payload carried the upstream run id
        upstream = list(Flow("LinearFlow"))[0]
        assert task["upstream_run"].data == upstream.id
        assert task["n_events"].data == 1

        # the bus cursor advanced: A's event is consumed exactly once
        # (B's own run-finished is on the bus now, but B doesn't subscribe
        # to itself)
        assert listener.poll_once() == []

    def test_external_event_triggers_flow(self, tpuflow_root):
        from metaflow_tpu.events import LocalTriggerListener, publish_event

        listener = LocalTriggerListener(env=_env(tpuflow_root))
        names = listener.register(
            os.path.join(FLOWS, "event_trigger_flow.py")
        )
        assert names == ["data_ready"]

        publish_event("data_ready", payload={"path": "gs://bucket/day=7"})
        launched = listener.poll_once()
        assert len(launched) == 1
        assert launched[0][1] == 0

        from metaflow_tpu.client import Flow, namespace

        namespace(None)
        task = list(Flow("EventTriggerFlow"))[0]["start"].task
        assert task["event_name"].data == "data_ready"
        assert task["path"].data == "gs://bucket/day=7"

    def test_unrelated_event_does_not_launch(self, tpuflow_root):
        from metaflow_tpu.events import LocalTriggerListener, publish_event

        listener = LocalTriggerListener(env=_env(tpuflow_root))
        listener.register(os.path.join(FLOWS, "event_trigger_flow.py"))
        publish_event("some_other_event")
        assert listener.poll_once() == []


class TestSensorCompile:
    def test_sensor_maps_event_body_into_workflow(self, tpuflow_root):
        """The Sensor must carry the event data into the submitted
        workflow (else current.trigger is None in-cluster)."""
        import yaml

        proc = subprocess.run(
            [sys.executable, os.path.join(FLOWS, "event_trigger_flow.py"),
             "--datastore", "local", "--datastore-root", tpuflow_root,
             "argo-workflows", "create", "--only-json"],
            env=_env(tpuflow_root), capture_output=True, text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        docs = [d for d in yaml.safe_load_all(proc.stdout) if d]
        sensor = next(d for d in docs if d.get("kind") == "Sensor")
        awf = sensor["spec"]["triggers"][0]["template"]["argoWorkflow"]
        # parameters live on argoWorkflow (workflow-relative dest), not on
        # the TriggerTemplate where the CRD would reject them
        (param,) = awf["parameters"]
        assert param["src"] == {"dependencyName": "data_ready",
                                "dataKey": "body"}
        assert param["dest"] == "spec.arguments.parameters.0.value"
        wf = awf["source"]["resource"]
        assert wf["spec"]["arguments"]["parameters"][0]["name"] == \
            "trigger-events-0"
        # the WorkflowTemplate forwards the parameter into pod env
        template = next(d for d in docs
                        if d.get("kind") == "WorkflowTemplate")
        start = next(t for t in template["spec"]["templates"]
                     if t["name"] == "start")
        env_names = [e["name"] for e in start["container"]["env"]]
        assert "TPUFLOW_TRIGGER_EVENTS" in env_names


class TestWebhookPublish:
    def test_publish_posts_to_argo_events_url(self, tpuflow_root,
                                              monkeypatch):
        """With TPUFLOW_ARGO_EVENTS_URL set, publish POSTs the event to
        the Argo Events webhook instead of the local bus."""
        import http.server
        import threading

        received = []

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                received.append(json.loads(self.rfile.read(n)))
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        server = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            monkeypatch.setenv(
                "TPUFLOW_ARGO_EVENTS_URL",
                "http://127.0.0.1:%d/" % server.server_port,
            )
            from metaflow_tpu.events import list_events, publish_event

            publish_event("deployed_event", payload={"k": "v"})
            assert len(received) == 1
            assert received[0]["name"] == "deployed_event"
            assert received[0]["payload"] == {"k": "v"}
            # webhook mode bypasses the local bus
            assert list_events() == []
        finally:
            server.shutdown()
