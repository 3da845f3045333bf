"""Traffic generators: deterministic by seed, the same multiset of
sizes for every seed, and the stated length distributions."""
import numpy as np
import pytest

from bench import draw, manifest

LOGN = {"lognormal": {"median": 96, "sigma": 1.2}, "min": 4, "max": 4096}


def test_stratified_same_multiset_other_order():
    a = draw.stratified(LOGN, 256, draw.rng_for(1, "x"))
    b = draw.stratified(LOGN, 256, draw.rng_for(2**40 + 3, "x"))
    assert sorted(a) == sorted(b) and a != b
    assert a == draw.stratified(LOGN, 256, draw.rng_for(1, "x"))


@pytest.mark.parametrize("dist, median, lo, hi", [
    (LOGN, 96, 4, 4096),
    ({"loguniform": {"min": 2048, "max": 8192}}, 4096, 2048, 8192),
    ({"lognormal": {"median": 128, "sigma": 0.8}, "min": 32, "max": 1024},
     128, 32, 1024),
])
def test_stated_median_and_bounds(dist, median, lo, hi):
    v = np.array(draw.stratified(dist, 1024, draw.rng_for(0, "m")))
    assert v.min() >= lo and v.max() <= hi
    assert abs(np.median(v) / median - 1) < 0.01


def test_mixture_shares_and_geometric_mean():
    mix = {"mixture": [
        {"share": 0.8, "lognormal": {"median": 256, "sigma": 0.8},
         "min": 32, "max": 1024},
        {"share": 0.2, "loguniform": {"min": 2048, "max": 8192}}]}
    v = np.array(draw.stratified(mix, 1000, draw.rng_for(5, "p")))
    assert (v >= 2048).sum() == 200
    g = draw.stratified({"geometric": {"mean": 2.5}}, 4000,
                        draw.rng_for(5, "g"))
    assert abs(np.mean(g) - 2.5) < 0.05
    z = draw.stratified({"zipf": {"n": 8, "a": 1.2}}, 800,
                        draw.rng_for(5, "z"))
    counts = np.bincount(np.array(z, int), minlength=8)
    assert counts[0] > counts[1] > counts[7] > 0


def _kind(name):
    cell = manifest.Cell("x", {}, {"kind": name}, 1, [], [],
                         manifest.BENCH_DIR)
    return manifest.traffic_kind(cell)


def _play(mod, params, seed, steps=200, dt=0.05):
    """Offer the source for steps * dt seconds, answering every turn at
    once with tokens drawn from the seed."""
    src = mod.make_source(params, seed, 1000, 8192)
    rng = np.random.default_rng(seed)
    sent = []
    for i in range(steps):
        for t in src.due(i * dt):
            sent.append((t.due, t.prompt.tolist(), t.out_tokens, t.stream))
            src.on_done(t, rng.integers(0, 1000, t.out_tokens).tolist(),
                        i * dt)
    return sent


CHAT = {"clients": 4, "stagger_s": 0.4, "context_cap": 8192,
        "system_prompts": {"count": 4, "length": {
            "loguniform": {"min": 16, "max": 64}},
            "popularity": {"zipf": {"n": 4, "a": 1.2}}},
        "turns": {"geometric": {"mean": 2.5}, "max": 8},
        "user_tokens": LOGN,
        "output_tokens": {"lognormal": {"median": 16, "sigma": 0.5}},
        "think_s": {"exponential": {"mean": 0.2}}}


@pytest.mark.parametrize("seed", [7, 2**40 + 7])
def test_sessions_deterministic_by_seed(seed):
    mod = _kind("sessions")
    a = _play(mod, CHAT, seed)
    assert a and a == _play(mod, CHAT, seed)
    assert a != _play(mod, CHAT, seed + 1)


def test_closed_loop_keeps_one_turn_per_client():
    """A client's next turn is due only after its reply: with replies
    held back, exactly ``clients`` turns are ever offered."""
    src = _kind("sessions").make_source(CHAT, 5, 1000, 8192)
    held = [t for i in range(200) for t in src.due(i * 0.05)]
    assert len(held) == CHAT["clients"]
    assert sorted(t.due for t in held) == pytest.approx([0, 0.1, 0.2, 0.3])
    for t in held:
        src.on_done(t, [1] * t.out_tokens, 10.0)
    assert len(src.due(1e9)) == CHAT["clients"]


def test_history_replays_the_traffic_shape():
    src = _kind("sessions").make_source(CHAT, 9, 1000, 8192)
    it = src.history()
    first = [next(it) for _ in range(64)]
    again = _kind("sessions").make_source(CHAT, 9, 1000, 8192).history()
    assert all(np.array_equal(h, next(again)) for h in first)
    systems = {tuple(s) for s in src.system}
    for h in first:
        assert any(tuple(h[:len(s)]) == s for s in systems)
        assert len(h) <= CHAT["context_cap"]
    # the history draws stand apart: the live traffic is unchanged
    live = _play(_kind("sessions"), CHAT, 9)
    assert live == _play(_kind("sessions"), CHAT, 9)


def test_sessions_resend_the_whole_transcript():
    mod = _kind("sessions")
    p = {"clients": 3, "context_cap": 8192,
         "system_prompts": {"count": 2, "length": {
             "loguniform": {"min": 16, "max": 32}},
             "popularity": {"zipf": {"n": 2, "a": 1.2}}},
         "turns": {"geometric": {"mean": 3.0}, "max": 8},
         "user_tokens": {"lognormal": {"median": 8, "sigma": 0.3}},
         "output_tokens": {"lognormal": {"median": 4, "sigma": 0.3}},
         "think_s": {"exponential": {"mean": 0.1}}}
    src = mod.make_source(p, 3, 1000, 8192)
    last = {}
    for i in range(400):
        for t in src.due(i * 0.05):
            if t.stream in last:
                prev_prompt, reply = last[t.stream]
                head = np.concatenate([prev_prompt, reply])
                assert np.array_equal(t.prompt[:len(head)], head)
            reply = np.arange(t.out_tokens, dtype=np.int32) + 100 * t.index
            last[t.stream] = (t.prompt, reply)
            src.on_done(t, reply.tolist(), i * 0.05)
    assert any(k for k in last)
