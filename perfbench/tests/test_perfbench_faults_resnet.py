"""resnet18-imagenet at a small input on the CPU: a sound run is
correct, the control (the reference in bfloat16, in the program's place)
is not, and a run with the forward broken underneath is not correct."""
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import loader

import _tiny

LIMIT = loader.limits("resnet18-imagenet.eval")


@pytest.fixture(scope="module")
def sound():
    sz, mix = _tiny.resnet()
    return _tiny.run_tiny("resnet18-imagenet.eval", sz, mix)


def test_sound_run_is_correct(sound):
    res, _ = sound
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"


def test_control_is_not_correct(sound):
    _, cell = sound
    got = cell.check(LIMIT, controls=(LIMIT["control"],))[LIMIT["control"]]
    assert any(got[n] > limit for n, limit in LIMIT["limits"].items()), got


def test_every_value_before_the_pool_is_exact(sound):
    """What keeps the program and the reference bit for bit alike up to
    the average pool: power-of-two CIM scales and folded BN."""
    _, cell = sound
    for ls in cell.scales.values():
        for v in (ls["s_a"], ls["s_p"]):
            mant, _ = np.frexp(np.asarray(v))
            assert (mant == 0.5).all()
    for st in cell.bn.values():
        assert not np.asarray(st["mean"]).any()
        assert (np.asarray(st["var"]) + np.float32(1e-5) == 1.0).all()


def _answer_altered(forward):
    def fwd(*a, **kw):
        logits, state = forward(*a, **kw)
        return jnp.roll(logits, 1, axis=-1), state
    return fwd


def _half_batch(forward):
    def fwd(params, state, x, cfg, **kw):
        half = x.shape[0] // 2
        logits, state = forward(params, state, x[:half], cfg, **kw)
        return jnp.concatenate([logits] * 2), state
    return fwd


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch])
def test_broken_path_is_not_correct(fault, monkeypatch):
    from repro.models import resnet
    monkeypatch.setattr(resnet, "forward", fault(resnet.forward))
    sz, mix = _tiny.resnet()
    res, _ = _tiny.run_tiny("resnet18-imagenet.eval", sz, mix)
    assert res["correct"] is False, res["checks"]
