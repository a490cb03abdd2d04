"""A linear-attention and block-sparse checkpoint (``tiny_sala_header``) through
the real writer, the loader and the scheduler on the CPU: admissions into
lanes that served before ride fused steps and give the tokens they give
alone; what is counted (tests/test_sala_engine.py has the engine's cases)."""

import pytest

from distributed_llama_multiusers_tpu.formats.synthetic import tiny_sala_header

import latent_toy


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A synthetic ``S L L S`` checkpoint through the real writer and loader."""
    return latent_toy.serving(tiny_sala_header("SLLS"), tmp_path_factory.mktemp("sala"), scale=0.1)


def test_a_loaded_checkpoint_serves_and_admissions_reuse_lanes(served):
    """Six requests on two lanes, the longest past dense_len (48): four are
    admitted into lanes that served before, by fused steps, and give the
    tokens they give alone (the same warmed engine under a scheduler with the
    pipelined loop and fused admissions off)."""
    shared = "the same long opening words of two requests, and more of them, "
    prompts = [shared + "then one end", shared + "then another", "ab ab ab ab ab ab",
               "hello world hello", "lo lo lo world", shared + "and a third"]
    tokens, stats = served.serve(prompts)
    assert stats["state_zero_starts"] == 6 and stats["jit_compiles_after_warmup"] == 0
    assert stats["prefix_hits"] == 0 and stats["prefix_tokens_saved"] == 0
    assert stats["spec_steps"] == 0 and stats["pipeline_flushes"] == 0 and stats["fused_steps"] > 0
    # two linear layers of 4 heads of 16 x 16 float32, in and out, a live lane a step
    assert stats["linear_state_bytes_moved"] > 0
    assert stats["linear_state_bytes_moved"] % (2 * 2 * 4 * 16 * 16 * 4) == 0
    assert 0 < stats["attn_blocks_read"] < stats["attn_blocks_held"]
    assert stats["sparse_lane_steps"] > 0
    for i in (0, 2, 5):  # a first admission, a short one, one into a lane that served twice
        alone, off = served.serve([prompts[i]], pipelined=False, fused_prefill=False)
        assert alone[0] == tokens[i], (i, prompts[i])
        assert off["jit_compiles_after_warmup"] == 0 and off["fused_steps"] == 0
