"""The harness, driven without a chip, must judge a broken timed path not
correct: once per fault a serving cell can have.  The decode step is
broken underneath the engine (through the program's shared step table),
so submit / tick / the window / the comparison all run as on the chip.
A four-chip fault (the exchange left out) has no cell here."""
import jax.numpy as jnp
import pytest

from repro.serve import engine as engine_mod

from .tiny import run, tiny_cell

CELLS = ["granite-3-2b.chat-poisson", "opt-6.7b.long-decode"]


def _cell(name):
    if name.endswith("long-decode"):
        # outputs of 64-128 tokens: at 512 tokens of context (32-64) the
        # dropped KV of a decode step read 0.055-0.289 over four seeds,
        # both sides of the limit; at 1024, 0.116-0.296
        return tiny_cell(name, context=1024, pages=65, page_size=32,
                         chunk=128)
    return tiny_cell(name)


def _broken(fault):
    def make(orig):
        def fused(params, cache, tokens, lens, live, key):
            new_cache, new_tokens, new_lens = orig(params, cache, tokens,
                                                   lens, live, key)
            if fault == "state_unchanged":
                # the KV written by this step is dropped
                return cache, new_tokens, new_lens
            if fault == "half_batch":
                # every other row of the batch is left out
                half = jnp.arange(live.shape[0]) % 2 == 0
                keep = live & half
                return (new_cache, jnp.where(keep[:, None], new_tokens,
                                             tokens), new_lens)
            if fault == "token_altered":
                vocab = params["tok"]["embed"].shape[0]
                alt = (new_tokens + 1) % vocab
                return new_cache, jnp.where(live[:, None], alt,
                                            new_tokens), new_lens
            raise ValueError(fault)
        return fused
    return make


@pytest.fixture
def fault(monkeypatch, request):
    orig_shared = engine_mod._shared_steps

    def shared(*a, **kw):
        steps = dict(orig_shared(*a, **kw))
        steps["decode_fused"] = _broken(request.param)(steps["decode_fused"])
        return steps

    monkeypatch.setattr(engine_mod, "_shared_steps", shared)
    return request.param


@pytest.mark.parametrize("name", CELLS)
def test_sound_path_is_correct(name):
    res = run(_cell(name), 2 ** 33 + 5)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"], indirect=True)
def test_fault_is_not_correct(name, fault):
    res = run(_cell(name), 2 ** 33 + 5)
    assert not res["correct"], (fault, res["checks"])
