"""A delta-rule checkpoint (``tiny_delta_header``) through the real writer, the
loader and the scheduler on the CPU: admissions into lanes that served before
ride fused steps (zero starts) and give the tokens they give alone; what is
counted and declined (tests/test_delta_engine.py has the engine's cases)."""

import pytest

from distributed_llama_multiusers_tpu.formats.synthetic import tiny_delta_header

import latent_toy


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A synthetic ``G D D D`` checkpoint through the real writer and loader."""
    # (the pipelined loop takes the multi-step programs' place: none is warmed, none compiles)
    return latent_toy.serving(tiny_delta_header("GDDD"), tmp_path_factory.mktemp("delta"),
                              scale=0.1, buckets=(16,), multi_step=0)


def test_a_loaded_checkpoint_serves_and_admissions_reuse_lanes(served):
    """Six requests on two lanes: four are admitted into lanes that served
    before, by fused steps, and the last, admitted into a lane that served
    twice, gives the tokens the same prompt gave as the first in a lane nothing
    had touched (one engine, one warm-up: the tier-1 clock has no room for a
    second)."""
    shared = "the same long opening words of two requests, and more of them, "
    prompts = [shared + "then one end", shared + "then another", "ab ab ab ab ab ab",
               "hello world hello", "lo lo lo world", shared + "then one end"]
    tokens, stats = served.serve(prompts)
    assert stats["state_zero_starts"] == 6 and stats["jit_compiles_after_warmup"] == 0
    assert stats["prefix_hits"] == 0 and stats["prefix_tokens_saved"] == 0
    assert stats["spec_steps"] == 0 and stats["pipeline_flushes"] == 0 and stats["fused_steps"] > 0
    # three delta layers of 4 heads of 16 x 16 float32, in and out, a live lane a step
    assert stats["delta_state_bytes_moved"] > 0
    assert stats["delta_state_bytes_moved"] % (2 * 3 * 4 * 16 * 16 * 4) == 0
    assert stats["delta_rows_computed"] > 0 and stats["delta_rows_computed"] % 3 == 0
    assert stats["linear_state_bytes_moved"] == 0 and stats["moe_assignments"] > 0
    assert tokens[5] == tokens[0] and tokens[1] != tokens[0]
