"""The seam between the harness and an architecture: `cells.load_family`.

The Llama family behind it gives, for a seed, the planes and the reference
logits that the harness's own generator and reference gave before the seam
was cut (the values below were recorded on the parent commit of PR 28)."""
import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import control
from harness import cells, correct
from harness.cells import BENCH_DIR, FAMILY_EXPORTS, ROOT

REHEARSAL = os.path.join(BENCH_DIR, "tests", "rehearsal")
SEED = 2_900_000_017
# sha256 over the Q40 planes (names sorted; nibble pairs, then scales):
# integer draws, the same on any machine
PLANES = {
    "tiny": "3cb1c09df741cda391ed040c5bca8b05c283a1b5655ce205862c43991b685d1d",
    "tiny_bias": "3bf89c60281b8176272bf1b41e80209ed4a6d2151c18f826db461af2f38f076c",
}
# the reference's logits for `correct.sample_sequences(cfg, SEED)`: every 61st
# of the flattened [2, 6, vocab] array's first twelve, and its norm
LOGITS = {
    "tiny": ([-1.9739060401916504, -3.121094226837158, 1.865505337715149, 0.7398298382759094,
              -1.2182090282440186, 0.7103052735328674, 0.5938023924827576, 4.128033638000488,
              3.955343008041382, -3.146655797958374, 0.5098438262939453, 1.6505767107009888],
             101.61566925048828),
    "tiny_bias": ([-2.3169608116149902, 1.9628366231918335, -3.9774301052093506,
                   0.37907782196998596, 2.5160210132598877, -0.9085606336593628,
                   -0.2186584323644638, -0.9035317897796631, 0.9445276260375977,
                   -6.240939617156982, -3.2185091972351074, -0.9007918834686279],
                  112.610595703125),
}


def _rehearsal_cfg(name):
    with open(os.path.join(REHEARSAL, "configs", name + ".json")) as f:
        return json.load(f)


def test_both_configurations_load_the_llama_family_by_default():
    bench = cells.load_benchmark()
    for entry in bench["configs"]:
        cfg = cells.load_config_file(bench, entry["name"])
        assert "family" not in cfg  # the files are as they were
        family = cells.load_family(cfg)
        assert family.__file__ == os.path.join(BENCH_DIR, "families", "llama.py")
        assert all(callable(getattr(family, f)) for f in FAMILY_EXPORTS)
        config = family.program_config(cfg)
        assert (config.vocab_size, config.seq_len) == (
            cfg["vocab_size"], cfg["max_position_embeddings"])


@pytest.mark.parametrize("name", ["tiny", "tiny_bias"])
def test_a_seed_gives_the_planes_and_logits_it_gave_before_the_seam(name):
    cfg = _rehearsal_cfg(name)
    family = cells.load_family(cfg)
    tensors = family.device_weights(family.program_config(cfg), SEED, jnp.float32)
    h = hashlib.sha256()
    for key in sorted(tensors):
        if hasattr(tensors[key], "packed"):
            h.update(key.encode())
            h.update(np.asarray(tensors[key].packed).tobytes())
            h.update(np.asarray(tensors[key].scales).tobytes())
    assert h.hexdigest() == PLANES[name]
    prompts, forced = correct.sample_sequences(cfg, SEED)
    prefixes = [correct.prefix_lengths(cfg, len(p)) for p in prompts]
    logits = correct.plain_logits(family, cfg, tensors, prompts, forced, prefixes)
    sample, norm = LOGITS[name]
    np.testing.assert_allclose(logits[:, :, ::61].ravel()[:12], sample, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(logits), norm, rtol=1e-5)


def test_a_family_that_lacks_a_function_is_refused_by_name(tmp_path):
    text = open(os.path.join(BENCH_DIR, "families", "llama.py")).read()
    (tmp_path / "no_state.py").write_text(
        text.replace("def lane_state_rel_err(", "def kv_rel_err("))
    (tmp_path / "whole.py").write_text(text)
    rel = os.path.relpath(tmp_path, ROOT)
    with pytest.raises(SystemExit, match="no_state.*does not export lane_state_rel_err"):
        cells.load_family({"family": "no_state"}, rel)
    whole = cells.load_family({"family": "whole"}, rel)
    assert os.path.samefile(whole.__file__, tmp_path / "whole.py")
    with pytest.raises(SystemExit, match="no family 'absent'"):
        cells.load_family({"family": "absent"}, rel)
    # a directory of the benchmark file's own comes first, the benchmark's after
    assert cells.load_family({}, rel).__file__.endswith(os.path.join("families", "llama.py"))


def test_a_second_family_is_compared_with_its_own_reference():
    """In one process what `test_fourth_cell.py` drives as a command: the toy
    mixture's engine against the family's reference, and against that
    reference routing to one expert a token."""
    cfg = _rehearsal_cfg("tiny_moe")
    family = cells.load_family(cfg, os.path.relpath(os.path.join(REHEARSAL, "families"), ROOT))
    assert family.program_config(cfg).n_experts == 4
    sound = control.readings(family, cfg, "as_configured", [31, 3_000_000_033], log=lambda s: None)
    assert all(r["ok"] for r in sound), sound
    assert all(r["route_kv_rel_err"] == 0.0 and r["decode_rel_err"] < 1e-5 for r in sound)

    class OneExpert:
        def __getattr__(self, name):
            return getattr(family, name)

        def reference_logits(self, c, *args, **kw):
            return family.reference_logits(dict(c, num_experts_per_tok=1), *args, **kw)

    wrong = control.readings(OneExpert(), cfg, "as_configured", [31], log=lambda s: None)
    assert not wrong[0]["ok"] and wrong[0]["decode_rel_err"] > 0.01
    lossy = control.readings(family, cfg, "reference_in_f8", [31], log=lambda s: None)
    assert not lossy[0]["ok"]
