"""With the timed path broken underneath, a run's ``correct`` comes out
false: a token altered where it is produced, a step whose cache write
is lost (the state it hands on unchanged from empty), and half of each
decode batch answered with another row's token.  The exchange between
chips does not exist in these one-chip cells."""
import time

import jax
import pytest

from bench import harness, manifest
from bench.tests import tiny


def alter_token(engine, loop):
    mixed = engine.step_mixed

    def step_mixed(*a, **k):
        res = mixed(*a, **k)
        s = next(iter(res.tokens))
        res.tokens[s] = (res.tokens[s] + 1) % engine.cfg.vocab_size
        return res
    engine.step_mixed = step_mixed


def lose_cache_write(engine, loop):
    ar = engine.arena
    replace = ar.replace
    ar.replace = lambda new: replace(jax.tree.map(lambda a: a * 0, new))


def half_batch(engine, loop):
    decode = engine.decode_batch

    def decode_batch(sessions, tokens, steps=1):
        out = decode(sessions, tokens, steps)
        keep = list(sessions)[: max(1, len(sessions) // 2)]
        for i, s in enumerate(sessions):
            if s not in keep:
                out[s] = [(t + 1) % engine.cfg.vocab_size
                          for t in out[keep[0]]]
        return out
    engine.decode_batch = decode_batch


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("faults"))


@pytest.mark.parametrize("fault", [None, alter_token, lose_cache_write,
                                   half_batch])
def test_fault_makes_correct_false(man, fault):
    cell = manifest.load_cell("tiny.chat", man)
    out = harness.run(cell, 2**33 + 5, 1.0, False, time.monotonic(),
                      fault=fault)
    assert out["correct"] is (fault is None), out["checks"]
