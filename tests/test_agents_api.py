"""Listing-1 API integration: a two-stage workflow runs through the real
engine with identifier propagation and workflow reconstruction."""
import pytest

from repro.agents import BaseAgent, Workflow
from repro.configs import get_config
from repro.serving import ServingConfig


class Stage1(BaseAgent):
    def _run_impl(self, input_data, metadata):
        toks = self.generate(self.encode_prompt("stage one", 10), metadata,
                             max_new_tokens=3)
        return {"x": len(toks)}, "Stage2"


class Stage2(BaseAgent):
    def _run_impl(self, input_data, metadata):
        toks = self.generate(self.encode_prompt("stage two", 14), metadata,
                             max_new_tokens=4)
        return {"done": True, "x": input_data["x"], "y": len(toks)}, None


@pytest.mark.slow
def test_two_stage_workflow_end_to_end():
    wf = Workflow(app_name="test", config=ServingConfig(
        n_instances=1, num_blocks=64, block_size=8, max_batch=4))
    wf.add_engine("e0", model=get_config("qwen3-1.7b").reduced())
    wf.add_agent("Stage1", Stage1)
    wf.add_agent("Stage2", Stage2)
    ids = [wf.submit_task("Stage1", {"q": i}) for i in range(3)]
    results = wf.run(timeout=120)
    assert len(results) == 3
    for mid in ids:
        assert results[mid] == {"done": True, "x": 3, "y": 4}
    # identifiers propagated: the orchestrator saw both stages and the edge
    wf.orch.analyzer  # traces were finalized on completion
    g = wf.orch.analyzer.graphs["test"]
    assert ("Stage1", "Stage2") in g.edges
    assert g.remaining_stages("Stage1") == 2
    # latency distributions collected per agent
    assert set(wf.orch.profiler.agents()) == {"Stage1", "Stage2"}
