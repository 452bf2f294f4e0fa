"""Generative graphs x contexts matrix (reference: test/core pattern),
including storage (gs over a fake server) and metadata (REST service)
provider contexts: the linear, branch, foreach and switch graphs."""

import pytest

from harness import GRAPHS, GRAPH_GROUPS, matrix, run_generated_flow


@pytest.mark.parametrize("graph_name,context_name", matrix("simple"))
def test_generated_flow(graph_name, context_name, run_flow, tpuflow_root,
                        tmp_path):
    run_generated_flow(graph_name, context_name, run_flow, tpuflow_root,
                       tmp_path)


def test_groups_hold_every_graph_once():
    """No documented-impossible combos exist: every graph shape must survive
    every provider/CLI/scheduler variation, in one of the three files."""
    grouped = sorted(g for gs in GRAPH_GROUPS.values() for g in gs)
    assert grouped == sorted(GRAPHS)
