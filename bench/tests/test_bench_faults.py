"""``correct`` comes out false when the timed path is broken underneath,
and the float8 control, judged in the served tokens' place by the same
numbers and limits, comes out not correct where the sound program passes.

Each test drives a whole run of a CPU-sized cell through the harness (its
look for a chip skipped), with the program patched at run time: the
decode megastep returning its KV state unchanged, half of the batch left
out of the routed experts, a token altered where the megastep produces
it. The cell has one chip, so no exchange between chips can be left out.
"""
import jax.numpy as jnp
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("faults")
    return tiny.make_checkout(tmp, "tiny_closed"), tmp / "weights"


@pytest.fixture
def fresh_programs():
    """The engine's jitted programs are built anew around each patch."""
    from repro.serving import engine

    engine._jitted_steps.cache_clear()
    yield
    engine._jitted_steps.cache_clear()


def _failed_numbers(res):
    return [k for k, c in res["checks"].items() if not c["value"] <= c["limit"]]


def test_sound_run_passes_and_the_control_fails(checkout):
    root, cache = checkout
    res = tiny.run_tiny(root, cache)
    assert res["correct"] and not _failed_numbers(res)
    ctrl = tiny.run_tiny(root, cache, control=True)
    assert not ctrl["correct"] and "mean_gap" in _failed_numbers(ctrl)


def _patch_decode(monkeypatch, change):
    from repro.models import transformer as tf

    orig = tf.paged_decode_horizon

    def patched(*args, **kw):
        return change(args[1], *orig(*args, **kw))

    monkeypatch.setattr(tf, "paged_decode_horizon", patched)


def test_decode_that_returns_its_state_unchanged_fails(checkout, monkeypatch,
                                                        fresh_programs):
    _patch_decode(monkeypatch, lambda cache, new, toks, emits, info:
                  (dict(new, k=cache["k"], v=cache["v"]), toks, emits, info))
    res = tiny.run_tiny(*checkout)
    assert not res["correct"] and _failed_numbers(res)


def test_half_of_the_batch_left_out_of_the_experts_fails(checkout, monkeypatch,
                                                         fresh_programs):
    from repro.core import compressed_moe

    orig = compressed_moe.combine

    def half(yp, dest, valid, gflat, t, k):
        y = orig(yp, dest, valid, gflat, t, k)
        return y.at[t // 2:].set(0)

    monkeypatch.setattr(compressed_moe, "combine", half)
    res = tiny.run_tiny(*checkout)
    assert not res["correct"] and _failed_numbers(res)


def test_token_altered_where_it_is_produced_fails(checkout, monkeypatch,
                                                  fresh_programs):
    def alter(cache, new, toks, emits, info):
        bumped = jnp.where(emits[0], (toks[0] + 1) % 512, toks[0])
        return new, toks.at[0].set(bumped), emits, info

    _patch_decode(monkeypatch, alter)
    res = tiny.run_tiny(*checkout)
    assert not res["correct"] and "mean_gap" in _failed_numbers(res)
