"""Every generated graph compiled to Argo Workflows and run by the simulator
(the production-scheduler dimension of the graphs x contexts matrix)."""

import os

import pytest

from harness import GRAPHS, _check_run, generate_flow


# reference: the argo-kubernetes leg of test/ux
@pytest.mark.parametrize("graph_name", sorted(GRAPHS))
def test_generated_flow_on_argo(graph_name, run_flow, tpuflow_root,
                                tmp_path):
    from argo_sim import ArgoSimulator
    from test_argo_e2e import _pod_env

    graph = GRAPHS[graph_name]
    flow_name = "Argo%sFlow" % graph_name.title().replace("_", "")
    src = generate_flow(graph, flow_name)
    flow_file = str(tmp_path / ("%s.py" % flow_name))
    with open(flow_file, "w") as f:
        f.write(src)

    # compile via the same fixture every other flow invocation uses
    proc = run_flow(flow_file, "--datastore", "local", "--datastore-root",
                    tpuflow_root, "argo-workflows", "create")
    import yaml

    manifest = next(iter(yaml.safe_load_all(proc.stdout)))
    env = _pod_env(tpuflow_root)
    # hermetic blob cache, like the run_flow fixture (conftest.py)
    env["TPUFLOW_CLIENT_CACHE"] = os.path.join(tpuflow_root, "blobcache")
    sim = ArgoSimulator(
        # a real workflow name is DNS-1123 (no underscores) — the sim's
        # JobSet name validation relies on that
        manifest, workflow_name="wf-h-%s" % graph_name.replace("_", "-"),
        env=env,
        cwd=str(tmp_path), output_dir=str(tmp_path / "argo-outputs"),
    )
    sim.run()
    _check_run(flow_name, graph, tpuflow_root, {})
