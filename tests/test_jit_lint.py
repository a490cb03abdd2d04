"""dlint v4 (jit-stability / donation-discipline / warmup-coverage): the
device-program surface model and its verdict on the real tree.

Two layers, the PR-2 contract test_dlint.py established:

- **self-tests** — every new checker gets known-bad and known-good
  fixture snippets (waiver syntax included), so the analyzer is
  regression-tested as a program;
- **rot-guards over the real module** — the extracted surface of
  ``runtime/engine.py`` is pinned (>= 14 jit sites, the full family
  set, every family warmed, bucketed families warmed per bucket,
  donation discipline at every call site), so a refactor that silently
  drops a family out of the model — or out of warmup — fails tier-1
  here even before the package-wide lint runs.

Pure-stdlib imports: these tests run without jax.
"""

from __future__ import annotations

import textwrap
from pathlib import Path

from distributed_llama_multiusers_tpu.analysis import (
    PACKAGE_ROOT,
    Analyzer,
    default_checkers,
)
from distributed_llama_multiusers_tpu.analysis.cli import main as dlint_main
from distributed_llama_multiusers_tpu.analysis.jitmodel import jit_model_of


def run_on(tmp_path: Path, files: dict[str, str]):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src), encoding="utf-8")
    analyzer = Analyzer(default_checkers())
    return analyzer.run([tmp_path], baseline=set(), root=tmp_path)


def checks_of(findings):
    return sorted(f.check for f in findings)


def only(findings, check):
    """The donation fixtures are intentionally minimal (families, no
    warmup_engine), so warmup-coverage fires alongside by design —
    scope the assertion to the check under test."""
    return [f for f in findings if f.check == check]


# -- jit-stability ------------------------------------------------------------

STABILITY_HEADER = """
    import jax
    import jax.numpy as jnp

    class Engine:
        def __init__(self, row):
            self.cache = None
            self._table_sharding = None
            self._host_tables = row

        def _replace_leaf(self, host_array, sharding):
            if sharding is None:
                return jnp.asarray(host_array)
            return jax.make_array_from_callback(
                host_array.shape, sharding, lambda idx: host_array[idx]
            )
"""


def test_jit_stability_flags_bare_asarray_leaf(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": STABILITY_HEADER + """
        def apply(self, row):
            self.cache = self.cache._replace(table=jnp.asarray(row))
    """})
    assert checks_of(findings) == ["jit-stability"]
    assert "_replace_leaf" in findings[0].message


def test_jit_stability_flags_carry_rebuild_and_unsharded_device_put(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": STABILITY_HEADER + """
        def reseed(self, tokens):
            self._pl_carry = jnp.array(tokens)

        def upload(self, row):
            self._g_dev = jax.device_put(row)
    """})
    assert checks_of(findings) == ["jit-stability", "jit-stability"]


def test_jit_stability_sanctioned_constructor_clean(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": STABILITY_HEADER + """
        def apply(self, row):
            self.cache = self.cache._replace(
                table=self._replace_leaf(row, self._table_sharding)
            )

        def upload(self, row):
            self._g_dev = jax.device_put(row, self._table_sharding)
    """})
    assert findings == []


def test_jit_stability_operands_and_init_are_exempt(tmp_path):
    # converting OPERANDS is universal (never stored state), and __init__
    # builds the avals every program is compiled against
    findings = run_on(tmp_path, {"runtime/engine.py": """
        import jax.numpy as jnp

        class Engine:
            def __init__(self, row):
                self.cache = jnp.asarray(row)

            def decode(self, tokens):
                return self._fn(jnp.asarray(tokens))
    """})
    assert findings == []


def test_jit_stability_out_of_scope_file_ignored(tmp_path):
    findings = run_on(tmp_path, {"serving/other.py": STABILITY_HEADER + """
        def apply(self, row):
            self.cache = jnp.asarray(row)
    """})
    assert findings == []


# -- donation-discipline ------------------------------------------------------

DONATE_HEADER = """
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=(1,))
    def _decode(params, cache, tokens):
        return tokens, cache

    class Engine:
        def __init__(self):
            self._decode_fn = _decode
"""


def test_donation_flags_use_after_donate(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": DONATE_HEADER + """
        def decode(self, tokens):
            toks, fresh = self._decode_fn(self.params, self.cache, tokens)
            junk = self.cache.k
            return toks
    """})
    dona = only(findings, "donation-discipline")
    assert len(dona) == 1
    assert "use-after-donate" in dona[0].message
    assert "'self.cache'" in dona[0].message


def test_donation_flags_escape_into_host_state(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": DONATE_HEADER + """
        def decode(self, tokens):
            self._stash = self.cache
            toks, self.cache = self._decode_fn(
                self.params, self.cache, tokens
            )
            return toks
    """})
    dona = only(findings, "donation-discipline")
    assert len(dona) == 1
    assert "escapes" in dona[0].message


def test_donation_rebound_result_clean(tmp_path):
    # the engine's actual shape: the donated operand is rebound from the
    # call's results, later reads see the new buffer
    findings = run_on(tmp_path, {"runtime/engine.py": DONATE_HEADER + """
        def decode(self, tokens):
            toks, self.cache = self._decode_fn(
                self.params, self.cache, tokens
            )
            return self.cache.k
    """})
    assert only(findings, "donation-discipline") == []


def test_donation_star_operands_resolved(tmp_path):
    # `fn(*operands)` with a local tuple literal (the real decode()):
    # the donated slot is found through the expansion
    findings = run_on(tmp_path, {"runtime/engine.py": DONATE_HEADER + """
        def decode(self, tokens):
            operands = (self.params, self.cache, tokens)
            toks, fresh = self._decode_fn(*operands)
            junk = self.cache.k
            return toks
    """})
    assert len(only(findings, "donation-discipline")) == 1


def test_donation_moved_never_read_again_clean(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": DONATE_HEADER + """
        def consume(self, cache, tokens):
            toks, fresh = self._decode_fn(self.params, cache, tokens)
            return toks, fresh
    """})
    assert only(findings, "donation-discipline") == []


# -- warmup-coverage ----------------------------------------------------------

COVERAGE_HEADER = """
    from functools import partial
    import jax
    import numpy as np

    @partial(jax.jit, donate_argnums=(1,))
    def _decode(params, cache, tokens):
        return tokens, cache

    @partial(jax.jit, donate_argnums=(0,))
    def _copy_lane(cache, src, dst):
        return cache

    class Engine:
        def __init__(self):
            self._decode_fn = _decode
            self._copy_lane_fn = _copy_lane

        def decode(self, tokens):
            toks, self.cache = self._decode_fn(
                self.params, self.cache, tokens
            )
            return toks

        def copy_lane(self, src, dst):
            self.cache = self._copy_lane_fn(self.cache, src, dst)
"""


def test_warmup_coverage_flags_unwarmed_family(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": COVERAGE_HEADER + """
    def warmup_engine(engine):
        engine.decode(np.zeros(2))
    """})
    assert checks_of(findings) == ["warmup-coverage"]
    assert "_copy_lane_fn" in findings[0].message
    assert "copy_lane" in findings[0].message


def test_warmup_coverage_full_warmup_clean(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": COVERAGE_HEADER + """
    def warmup_engine(engine):
        engine.decode(np.zeros(2))
        engine.copy_lane(0, 1)
    """})
    assert findings == []


def test_warmup_coverage_getattr_alias_counts_as_warmed(tmp_path):
    # the real warmup's apply_paged = getattr(engine, "apply_paged_admit")
    findings = run_on(tmp_path, {"runtime/engine.py": COVERAGE_HEADER + """
    def warmup_engine(engine):
        engine.decode(np.zeros(2))
        copy = getattr(engine, "copy_lane", None)
        if copy is not None:
            copy(0, 1)
    """})
    assert findings == []


def test_warmup_coverage_flags_dead_family(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": """
        from functools import partial
        import jax
        import numpy as np

        @partial(jax.jit, donate_argnums=(1,))
        def _decode(params, cache, tokens):
            return tokens, cache

        @partial(jax.jit, donate_argnums=(1,))
        def _orphan(params, cache, tokens):
            return tokens, cache

        class Engine:
            def __init__(self):
                self._decode_fn = _decode
                self._orphan_fn = _orphan

            def decode(self, tokens):
                toks, self.cache = self._decode_fn(
                    self.params, self.cache, tokens
                )
                return toks

        def warmup_engine(engine):
            engine.decode(np.zeros(2))
    """})
    assert checks_of(findings) == ["warmup-coverage"]
    assert "dead device-program surface" in findings[0].message


def test_warmup_coverage_flags_missing_warmup_fn(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": COVERAGE_HEADER})
    assert checks_of(findings) == ["warmup-coverage"]
    assert "no warmup_engine" in findings[0].message


BUCKETED = """
    from functools import partial
    import jax
    import numpy as np

    @partial(jax.jit, donate_argnums=(1,))
    def _prefill(params, cache, tokens):
        return tokens, cache

    class Engine:
        prefill_buckets = (64, 256)

        def __init__(self):
            self._prefill_fn = _prefill

        def bucket_for(self, n):
            return 64

        def prefill_chunk(self, chunk):
            bucket = self.bucket_for(len(chunk))
            padded = np.zeros(bucket)
            toks, self.cache = self._prefill_fn(
                self.params, self.cache, padded
            )
            return toks
"""


def test_warmup_coverage_flags_bucketed_family_warmed_once(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": BUCKETED + """
    def warmup_engine(engine):
        engine.prefill_chunk([0] * 64)
    """})
    assert checks_of(findings) == ["warmup-coverage"]
    assert "prefill_buckets` loop" in findings[0].message


def test_warmup_coverage_bucket_loop_clean(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": BUCKETED + """
    def warmup_engine(engine):
        for bucket in engine.prefill_buckets:
            engine.prefill_chunk([0] * bucket)
    """})
    assert findings == []


def test_warmup_coverage_waivable_with_reason(tmp_path):
    # waive at the family's binding line (where the finding anchors)
    waived = run_on(tmp_path, {"runtime/engine.py": COVERAGE_HEADER
        .replace(
            "self._copy_lane_fn = _copy_lane",
            "self._copy_lane_fn = _copy_lane  "
            "# dlint: ok[warmup-coverage] debug-only path, never serves",
        ) + """
    def warmup_engine(engine):
        engine.decode(np.zeros(2))
    """})
    assert waived == []


# -- rot-guards over the real runtime/engine.py -------------------------------

ENGINE = PACKAGE_ROOT / "runtime" / "engine.py"


# the full dispatchable family set the serving loop can reach; a new
# `self.*_fn = jax.jit(...)`-style binding must join this list AND the
# warmup loop, or the package-wide lint (test_dlint) fails first
EXPECTED_FAMILIES = {
    "_decode_fn", "_decode_nologits_fn", "_decode_pl_fn",
    "_decode_spec_pl_fn", "_decode_spec_prefill_fn", "_decode_spec_fn",
    "_prefill_fn", "_decode_prefill_fn", "_copy_lane_fn", "_copy_page_fn",
    "_sample_one", "_make_decode_multi",
}


def test_real_engine_jit_site_count_floor():
    """The extractor still SEES the surface: >= 14 jax.jit sites in
    runtime/engine.py (12 families + the two init-time cache jits). A
    drop means the extraction idiom rotted, not that code disappeared."""
    model = jit_model_of(ENGINE)
    assert len(model.sites) >= 14, [s.name for s in model.sites]
    assert EXPECTED_FAMILIES <= set(model.families), (
        EXPECTED_FAMILIES - set(model.families)
    )


def test_real_engine_every_family_is_dispatched_and_warmed():
    """THE pin for the PR 11 compile-mid-chain class: every compiled
    family has a dispatcher, and every dispatcher set is covered by
    warmup_engine (copy_lane and sample_token joined warmup in this PR
    — the two adoption findings)."""
    model = jit_model_of(ENGINE)
    assert model.has_warmup
    warmed = model.warmed_families()
    groups: dict[int, list[str]] = {}
    for attr, site in model.families.items():
        groups.setdefault(id(site), []).append(attr)
    for attrs in groups.values():
        dispatchers = [
            d.name for d in model.dispatchers.values()
            if any(a in d.families for a in attrs)
        ]
        assert dispatchers, f"family {attrs} dispatched by nobody"
        assert any(a in warmed for a in attrs), (
            f"family {attrs} (dispatched by {dispatchers}) not warmed"
        )


def test_real_engine_warmed_method_set_pinned():
    model = jit_model_of(ENGINE)
    expected = {
        "prefill_chunk", "decode", "decode_spec", "decode_multi",
        "decode_pipelined", "decode_prefill_fused",
        "decode_spec_pipelined", "decode_spec_prefill_fused",
        "apply_paged_admit", "copy_lane", "sample_token",
    }
    assert expected <= set(model.warmed), expected - set(model.warmed)
    # bucketed families compile per prefill bucket: their warmup calls
    # must sit inside the `for bucket in engine.prefill_buckets` loop
    for m in ("prefill_chunk", "decode_prefill_fused",
              "decode_spec_prefill_fused"):
        assert model.warmed[m].in_bucket_loop, m
        assert model.dispatchers[m].bucketed, m


def test_real_engine_donation_discipline_holds():
    """Every donate_argnums call site in the real engine rebinds the
    donated operand from the call's results (>= 10 sites modeled — the
    whole decode/prefill/copy family donates its cache)."""
    model = jit_model_of(ENGINE)
    uses = [u for d in model.dispatchers.values() for u in d.donate_calls]
    assert len(uses) >= 10, len(uses)
    for use in uses:
        assert use.rebound, (use.family, use.line, use.spelling)
        assert use.escape_line is None, use


def test_real_engine_device_topk_knob_is_gone():
    """The dead knob warmup-coverage would mis-model stays deleted."""
    src = ENGINE.read_text(encoding="utf-8")
    import ast as _ast

    for node in _ast.walk(_ast.parse(src)):
        if isinstance(node, (_ast.FunctionDef, _ast.AsyncFunctionDef)):
            assert "device_topk" not in {a.arg for a in node.args.args}, (
                f"device_topk resurfaced on {node.name}"
            )


def test_jit_table_cli(capsys):
    assert dlint_main(["--jit-table"]) == 0
    out = capsys.readouterr().out
    assert "_decode_fn" in out and "warmup_engine" in out
    # every family row's warmed column reads "yes"
    assert not [l for l in out.splitlines() if l.endswith("NO")], out
