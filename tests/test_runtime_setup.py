"""Nothing on the serving path may make a CPU or reference run look like a
chip run (PR 21): the start-up line and /stats name the device and the
weight/kernel path, the compile cache lives where the rule says, and a
kernel or transport that was asked for fails loudly instead of being swapped
for its fallback."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from distributed_llama_multiusers_tpu.app import runtime_setup
from distributed_llama_multiusers_tpu.app.args import build_parser
from distributed_llama_multiusers_tpu.ops import linear, ring_collective
from distributed_llama_multiusers_tpu.runtime import ContinuousBatchingScheduler
from distributed_llama_multiusers_tpu.server import ApiServer
from distributed_llama_multiusers_tpu.telemetry import logs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", [None, "somewhere/else"])
def test_compile_cache_dir_rule(env_dir, monkeypatch, tmp_path,
                                restore_cache_config):
    """JAX_COMPILATION_CACHE_DIR set -> there (JAX reads it itself: the
    program configures no other); unset -> ONE fixed git-ignored directory
    inside the checkout, never one made from a pid, a time or a temp name."""
    monkeypatch.delenv("DLLAMA_NO_COMPILE_CACHE", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert runtime_setup.enable_compilation_cache() == os.path.join(
            REPO, ".jax_cache"
        )
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache"
        )
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        assert runtime_setup.enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir is None  # set no other
        assert not os.path.exists(want)  # nor made one


def test_cpu_pin_is_logged(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(logs._DEFAULT, "stream", sys.stdout)
    runtime_setup.honor_cpu_platform_env()
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["event"] == "platform_pinned" and rec["platform"] == "cpu"


def test_load_stack_reports_device_on_startup_line_and_stats(
    tiny_model, monkeypatch, capsys, restore_cache_config
):
    monkeypatch.setattr(logs._DEFAULT, "stream", sys.stdout)
    args = build_parser("dllama-api", api=True).parse_args([
        "--model", tiny_model["model"],
        "--tokenizer", tiny_model["tokenizer"], "--max-lanes", "2",
    ])
    _, params, tokenizer, engine = runtime_setup.load_stack(args)
    # the tree handed back is the one the engine serves from: no second copy
    # of a leaf the engine put at rest is held for the process's life
    assert params is engine.params
    line = next(
        json.loads(s) for s in capsys.readouterr().out.splitlines()
        if s.startswith('{"event": "runtime_device"')
    )
    dev = jax.devices()[0]
    want = {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()), "mesh_shape": None,
        # the CPU picks dense f32 weights and an f32 KV cache — and SAYS so
        "weights": "dense", "kv_dtype": "float32", "dequant_mode": "v4",
        "pallas_kernel": False, "ring_sync": False,
    }
    assert {k: line[k] for k in want} == want
    assert line["compile_cache_dir"] and line["load_s"] >= 0
    stats = ApiServer(
        ContinuousBatchingScheduler(engine, tokenizer), tokenizer
    ).handle_stats()
    assert {k: stats[k] for k in want} == want


class _FakeTpu:
    platform = "tpu"


@pytest.mark.parametrize("case", ["no_backend", "kernel_import", "switch"])
def test_kernel_selection_fails_loudly(case, monkeypatch):
    """ops/linear.py: a backend that does not answer, or a kernel module
    that does not import on a TPU, is an error — never the XLA dequant path
    unannounced. DLLAMA_NO_PALLAS=1 stays the one explicit switch."""
    monkeypatch.delenv("DLLAMA_NO_PALLAS", raising=False)
    linear._pallas_q40_matmul.cache_clear()
    try:
        if case == "no_backend":
            def devices(*_a):
                raise RuntimeError("Unable to initialize backend 'tpu'")

            monkeypatch.setattr(jax, "devices", devices)
            with pytest.raises(RuntimeError, match="initialize backend"):
                linear._pallas_q40_matmul()
        elif case == "kernel_import":
            monkeypatch.setattr(jax, "devices", lambda *_a: [_FakeTpu()])
            monkeypatch.setitem(
                sys.modules,
                "distributed_llama_multiusers_tpu.ops.pallas_q40", None,
            )
            with pytest.raises(ImportError):
                linear._pallas_q40_matmul()
        else:
            monkeypatch.setattr(jax, "devices", lambda *_a: [_FakeTpu()])
            monkeypatch.setenv("DLLAMA_NO_PALLAS", "1")
            assert linear._pallas_q40_matmul() is None
    finally:
        linear._pallas_q40_matmul.cache_clear()


def test_opted_in_rdma_hop_raises_instead_of_falling_back(monkeypatch):
    monkeypatch.setattr(ring_collective, "_use_rdma", lambda: True)

    def broken(*_a):
        raise NotImplementedError("Mosaic gap")

    monkeypatch.setattr(ring_collective, "_rdma_shift", broken)
    with pytest.raises(NotImplementedError, match="Mosaic gap"):
        ring_collective._shift(jnp.zeros((8,)), "tp", 2, rdma_ok=True)
