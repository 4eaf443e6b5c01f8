"""A serving cell from files the harness has never seen, run on the CPU
at a tiny size: sound, it is correct; with a token altered where the
engine produces it, it is not."""

import pytest

from . import cellrun


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("serve-checkout"))
    return tmp, cellrun.make_checkout(tmp, "tiny-serve")


def test_serving_cell_runs_and_is_correct(cell):
    rc, res = cellrun.run(*cell, seed=2**31 + 19, seconds=5)
    assert rc == 0 and res["correct"]
    assert set(res["metrics"]) == {"setup_s", "ttft_p50_ms", "itl_p95_ms"}
    assert res["checks"]["tokens_compared"]["value"] >= 5


def test_serving_cell_catches_an_altered_token(cell):
    rc, res = cellrun.run(*cell, seed=2**31 + 19, seconds=5,
                          fault="token_altered")
    assert rc == 0 and res["correct"] is False


def test_fp8_control_fails_the_limit():
    """At every position of a sequence, the token the float8 reference
    puts first lies further below the float32 reference's best than the
    limit allows, at one position at least."""
    import json
    import os

    import jax
    import jax.numpy as jnp

    from .configs import dense_decoder as ref
    here = os.path.dirname(os.path.abspath(__file__))
    cfg = json.load(open(os.path.join(here, "testdata", "smoke-tt.json")))
    mix = json.load(open(os.path.join(here, "testdata", "tiny-serve.json")))
    params = ref.init_params(cfg, jax.random.key(6))
    tok = jax.random.randint(jax.random.key(7), (64,), 0, 256)
    f32 = ref.logits(cfg, params, tok, "f32")
    pick = jnp.argmax(ref.logits(cfg, params, tok, "fp8"), -1)
    gap = jnp.max(f32, -1) - jnp.take_along_axis(f32, pick[:, None], -1)[:, 0]
    assert float(jnp.max(gap)) > mix["limits"]["logit_gap"]
