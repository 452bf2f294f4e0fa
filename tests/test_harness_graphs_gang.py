"""Generative graphs x contexts matrix (reference: test/core pattern),
including storage (gs over a fake server) and metadata (REST service)
provider contexts: the gang graphs."""

import pytest

from harness import matrix, run_generated_flow


@pytest.mark.parametrize("graph_name,context_name", matrix("gang"))
def test_generated_flow(graph_name, context_name, run_flow, tpuflow_root,
                        tmp_path):
    run_generated_flow(graph_name, context_name, run_flow, tpuflow_root,
                       tmp_path)
