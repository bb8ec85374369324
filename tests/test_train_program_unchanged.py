"""The train engine took a model as an argument (ISSUE 34) and left the GPT's
program as it was: the tiny GPT's lowered ``train_step`` (``gpt_hybrid.setup``,
one device, the ``names`` remat policy and the fused CE, as the train cells
run it) and the same under ``dp2 x tp2 + sp, zero1`` on four virtual devices,
as StableHLO text without locations, hash to what the parent commit's
(3f07615) hashed to. A change that means to alter the GPT's step records the
new hashes here and says why.

    JAX_PLATFORMS=cpu python tests/test_train_program_unchanged.py

prints the hashes of the tree it runs in."""
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

#: recorded on the parent commit by this file's ``__main__``
PARENT = {
    "gpt.train_step.one_device": "5fa9990fa260653b",
    "gpt.train_step.dp2_tp2_sp": "47b6ccfcef8c6cf1",
}

CASES = {
    "gpt.train_step.one_device": dict(dp=1, tp=1, sp=False),
    "gpt.train_step.dp2_tp2_sp": dict(dp=2, tp=2, sp=True),
}


def _text(name):
    from paddle_tpu.models import gpt_hybrid as gh
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig.tiny()
    pcfg = gh.ParallelConfig(
        remat_policy="names", param_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16, **CASES[name])
    n = pcfg.dp * pcfg.tp
    mesh, params, opt_state, step = gh.setup(cfg, pcfg, seed=3,
                                             devices=jax.devices()[:n])
    ids = jnp.zeros((4, 32), jnp.int32)
    with mesh:
        text = step.lower(params, opt_state, (ids, ids)).as_text()
    return re.sub(r"\s*loc\(.*?\)$", "", text, flags=re.M)


def hashes():
    return {name: hashlib.sha256(_text(name).encode()).hexdigest()[:16]
            for name in CASES}


@pytest.mark.parametrize("name", sorted(CASES))
def test_program_is_the_parents(name):
    got = hashlib.sha256(_text(name).encode()).hexdigest()[:16]
    assert got == PARENT[name]


if __name__ == "__main__":
    import json
    print(json.dumps(hashes(), indent=4))
