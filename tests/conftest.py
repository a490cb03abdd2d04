"""Test config: force JAX onto a virtual 8-device CPU mesh.

Multi-chip sharding is validated without TPU hardware the same way the
reference validates multi-node without a cluster (its NnFakeNodeSynchronizer
+ local process clusters, src/nn/nn-executor.cpp:6-8, examples/n-workers.sh):
here, XLA's host platform is split into 8 virtual devices and the real
collectives run through the same GSPMD paths they would take over ICI.
"""

import os

from distributed_llama_multiusers_tpu.utils.testing import force_cpu_mesh

force_cpu_mesh(n_devices=8)

import pytest  # noqa: E402


@pytest.fixture
def pallas_interpret():
    """The Pallas kernels on, in interpret mode, for one case: what it traces
    and builds meanwhile takes the kernels' paths."""
    from distributed_llama_multiusers_tpu.ops import linear

    linear.set_pallas_interpret(True)
    yield
    linear.set_pallas_interpret(False)


@pytest.fixture(scope="session")
def tiny_model(tmp_path_factory):
    """A tiny Q40 .m + .t pair on disk, shared across the session."""
    from distributed_llama_multiusers_tpu.formats.synthetic import (
        tiny_header,
        write_synthetic_model,
        write_synthetic_tokenizer,
    )

    d = tmp_path_factory.mktemp("tiny_model")
    header = tiny_header()
    model_path = str(d / "model.m")
    tok_path = str(d / "tokenizer.t")
    write_synthetic_model(model_path, header, seed=0)
    write_synthetic_tokenizer(tok_path, vocab_size=header.vocab_size)
    return {"model": model_path, "tokenizer": tok_path, "header": header}
