"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see 1 device (the dry-run sets its own 512-device flag in its own process).
"""
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig, SSMConfig, small_test_config
from repro.models.model import init_model


@pytest.fixture(scope="session")
def tiny_dense():
    return small_test_config("tiny-dense")


@pytest.fixture(scope="session")
def tiny_moe():
    return small_test_config(
        "tiny-moe", family="moe",
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64))


@pytest.fixture(scope="session")
def tiny_ssm():
    return small_test_config(
        "tiny-ssm", family="ssm",
        ssm=SSMConfig(d_state=16, headdim=16, chunk_size=8))


@pytest.fixture(scope="session")
def dense_params(tiny_dense):
    return init_model(jax.random.PRNGKey(0), tiny_dense)


@pytest.fixture(scope="session")
def moe_params(tiny_moe):
    return init_model(jax.random.PRNGKey(0), tiny_moe)


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(42)
