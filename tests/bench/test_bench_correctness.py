"""The comparison that decides ``correct``, shown to fail.

At smoke size on the CPU, through the same runners as the chip's runs
(minus the look for a chip): a sound run passes; the control (the plain
reference one precision lower) reads past the limit; and a run with the
timed path broken underneath comes out not correct, once for each fault
the cell can have: a step that returns its state unchanged, half of the
batch left out, and an answer altered where it is produced.  (One chip:
no exchange between chips to leave out.)
"""
import jax.numpy as jnp
import pytest

from bench_tiny import TINY_LIMITS, build_tiny_root, run_cell

SEED = 2 ** 33 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return build_tiny_root(tmp_path_factory.mktemp("bench"))


def _correct(out) -> bool:
    return out.failed == 0 and all(c.ok for c in out.checks)


# -- serving ------------------------------------------------------------------


def test_serving_sound_run_passes_and_its_control_fails(root):
    out, r = run_cell(root, "tiny.chat", SEED, control=True)
    assert out.attempted > 0 and _correct(out)
    assert r["sample_tokens"] >= 50
    assert r["control_widest_gap"] > TINY_LIMITS["tiny.chat"]["widest_gap"]


def _state_unchanged(orig):
    def f(self, params, cache, tokens, lens):
        nxt, logits, _ = orig(self, params, cache, tokens, lens)
        return nxt, logits, cache
    return f


def _half_batch(orig):
    def f(self, params, cache, tokens, lens):
        nxt, logits, new = orig(self, params, cache, tokens, lens)
        b = nxt.shape[0]
        return (jnp.where(jnp.arange(b)[:, None] < b // 2, nxt, tokens),
                logits, new)
    return f


def _token_altered(orig):
    def f(self, params, cache, tokens, lens):
        nxt, logits, new = orig(self, params, cache, tokens, lens)
        return (nxt + 1) % logits.shape[-1], logits, new
    return f


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_serving_fault_comes_out_not_correct(root, monkeypatch, fault):
    from repro.runtime.engine import ServingEngine
    monkeypatch.setattr(ServingEngine, "_step_fn",
                        fault(ServingEngine._step_fn))
    out, _ = run_cell(root, "tiny.chat", SEED)
    assert not _correct(out)


# -- calibration --------------------------------------------------------------


def test_calibration_sound_run_passes_and_its_control_fails(root):
    out, r = run_cell(root, "tiny.calib", SEED, method="fused",
                      control=True)
    assert out.attempted > 0 and _correct(out)
    assert r["control_level_mismatch"] > \
        TINY_LIMITS["tiny.calib"]["level_mismatch"]


def _levels_unchanged(orig):
    def f(inputs, noise, levels, offs, *a, **kw):
        return levels, jnp.zeros(levels.shape, jnp.float32)
    return f


def _half_grid(orig):
    def f(inputs, noise, levels, offs, *a, **kw):
        new, bias = orig(inputs, noise, levels, offs, *a, **kw)
        g = levels.shape[0]
        return jnp.where(jnp.arange(g)[:, None] < g // 2, new, levels), bias
    return f


@pytest.mark.parametrize("fault", [_levels_unchanged, _half_grid])
def test_calibration_fault_in_the_kernel_comes_out_not_correct(
        root, monkeypatch, fault):
    import repro.core.fleet as fleet
    monkeypatch.setattr(fleet, "calib_iter_fused",
                        fault(fleet.calib_iter_fused))
    out, _ = run_cell(root, "tiny.calib", SEED, method="fused")
    assert not _correct(out)


def test_calibration_answer_altered_comes_out_not_correct(root, monkeypatch):
    import repro.core.fleet as fleet
    orig = fleet.calibrate_fleet

    def altered(*a, **kw):
        res = orig(*a, **kw)
        res.levels = jnp.clip(res.levels + 1, 0, 7)
        return res
    monkeypatch.setattr(fleet, "calibrate_fleet", altered)
    out, _ = run_cell(root, "tiny.calib", SEED, method="fused")
    assert not _correct(out)
