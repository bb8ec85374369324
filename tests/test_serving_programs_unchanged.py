"""The cache's handle (``StaticCache``'s methods, ISSUE 32) left the lowered
serving programs of the models that were there as they were: the admit,
decode-block and block programs of tiny ``GPTForCausalLM`` and
``SDARMoeForCausalLM`` sessions, as StableHLO text without locations, hash
to what the parent commit's (7160f8b) hashed to. A change that means to
alter these programs records the new hashes here and says why.

    JAX_PLATFORMS=cpu python tests/test_serving_programs_unchanged.py

prints the hashes of the tree it runs in."""
import hashlib
import re

import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.decode import ContinuousBatchingSession

#: recorded on the parent commit by this file's ``__main__``
PARENT = {
    "gpt.admit": "905e96c2a13f825e",
    "gpt.decode_block": "9b76f77188b0c2d9",
    "sdar.admit_block": "362b51454946a706",
    "sdar.block": "a4fef3b54f571a07",
}


def _gpt_session():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    model = GPTForCausalLM(GPTConfig.tiny())
    return ContinuousBatchingSession(model, max_slots=3, max_length=32,
                                     decode_block=4)


def _sdar_session():
    from paddle_tpu.models.sdar_moe import SDARMoeConfig, SDARMoeForCausalLM
    paddle.seed(3)
    model = SDARMoeForCausalLM(SDARMoeConfig.tiny())
    return ContinuousBatchingSession(model, max_slots=3, max_length=32,
                                     generation="block_diffusion",
                                     denoising_steps=2)


def _text(jitted, *args):
    text = jitted.lower(*args).as_text()
    return re.sub(r"\s*loc\(.*?\)$", "", text, flags=re.M)


def programs():
    """name -> the program's text."""
    out = {}
    with _gpt_session() as s:
        state = [t._data for t in s._state_t]
        ids = jnp.zeros((1, 16), jnp.int32)
        lane = jnp.zeros((3,), jnp.int8)
        out["gpt.admit"] = _text(
            s._admit_jit, *state, ids, jnp.int32(5), jnp.int32(1),
            s._tokens, s._key, *s._cache_arrays)
        out["gpt.decode_block"] = _text(
            s._decode_blk_jit, *state, s._tokens, s._key, lane,
            *s._cache_arrays)
    with _sdar_session() as s:
        state = [t._data for t in s._state_t]
        ids = jnp.zeros((1, 16), jnp.int32)
        blk = jnp.zeros((3, 4), jnp.int32)
        lane = jnp.zeros((3,), jnp.int8)
        out["sdar.admit_block"] = _text(
            s._admit_blk_jit, *state, ids, jnp.int32(4), jnp.int32(1),
            *s._cache_arrays)
        out["sdar.block"] = _text(
            s._block_jit, *state, blk, blk.astype(bool), s._key, lane,
            *s._cache_arrays)
    return out


def hashes():
    return {name: hashlib.sha256(text.encode()).hexdigest()[:16]
            for name, text in programs().items()}


@pytest.fixture(scope="module")
def now():
    return hashes()


@pytest.mark.parametrize("name", sorted(PARENT))
def test_program_is_the_parents(now, name):
    assert now[name] == PARENT[name]


if __name__ == "__main__":
    import json
    print(json.dumps(hashes(), indent=4))
