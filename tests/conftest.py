import dataclasses

import jax
import pytest

from repro.configs import get_config, reduced
from repro.models import model as M
from repro.models.schema import init_params
from repro.perf import DEFAULT_PERF, replace as perf_replace

jax.config.update("jax_platforms", "cpu")

TINY_PERF = perf_replace(DEFAULT_PERF, scan_chunk=32, remat="none",
                         block_q=64, block_k=64)


def tiny_config(arch: str = "llama3.2-3b"):
    cfg = reduced(get_config(arch))
    return dataclasses.replace(cfg, dtype="float32")


@pytest.fixture(scope="session")
def tiny_llama():
    cfg = tiny_config("llama3.2-3b")
    params = init_params(M.param_schema(cfg), jax.random.PRNGKey(0), cfg.dtype)
    return cfg, params
