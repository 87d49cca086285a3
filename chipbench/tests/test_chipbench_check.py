"""The correctness check at a size a CPU test can hold: sound runs pass
the cell's limits; the control and each planted fault of the timed path
fail them.

The cell runs with 32 px images (its 14 clinics, SqueezeNet v1.1's
widths, hyper-parameters and limits unchanged) and the harness's look
for a chip skipped: the rest of a run goes as on the chip. On XLA:CPU
the program's float32 convolutions are exact, as they are on the chip
at "highest", and the limits were set from chip readings.
"""
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import calibrate, check, reference
from chipbench import run as bench

CELL = "sim-fit.squeezenet1_1.table1"
SIZE = 32     # the least side at which SqueezeNet v1.1's three pools fit


def make_ctx(seed: int):
    found = bench.resolve(bench.ROOT, CELL)
    args = types.SimpleNamespace(workload=CELL, seed=seed, seconds=0.05,
                                 trace=0)
    ctx = bench.Context(args, found["manifest"], found["cell"],
                        dict(found["config"], image_size=SIZE),
                        found["traffic"], found["limits"], found["model"],
                        bench.peaks_for("TPU v5 lite"), jax.devices()[:1])
    ctx.driver = found["driver"]
    return ctx


def test_sound_passes_and_control_fails():
    ctx = make_ctx(2**33 + 11)
    drv, calls = ctx.driver, ctx.traffic["check_calls"]
    changes = tuple(ctx.traffic["checked_changes"])
    su = drv.setup(ctx)
    args = (ctx, su.clinics, su.p0, su.round_key, calls)
    ref = drv.run_reference(*args)
    ok, checks = check.judge(check.compare(su.prog, ref, changes),
                             ctx.limits)
    assert ok, checks
    control = drv.run_reference(*args, **calibrate.STAND_INS[
        "control.1pass"])
    ok, checks = check.judge(check.compare(control, ref, changes),
                             ctx.limits)
    assert not ok, checks


def state_unchanged(build):
    """The program's trainer, its fit handing back the state it was
    given."""
    def broken(ctx, clinics, params):
        trainer = build(ctx, clinics, params)
        fit = trainer.fit

        def stale_fit(key, rounds=None):
            kept = jax.tree.map(jnp.copy, trainer.state)
            out = fit(key, rounds)
            trainer.state = kept
            return out
        trainer.fit = stale_fit
        return trainer
    return broken


class HalfBatchTrainer:
    """The reference in the trainer's place, training on half of each
    minibatch, the mean taken over the rest."""

    def __init__(self, ctx, clinics, params):
        self.kw = ctx.driver.round_kwargs(ctx, clinics,
                                          keep=ctx.config["batch"] // 2)
        self.swarm = reference.make_swarm(clinics)
        self.state = reference.fresh_state(params, jax.random.PRNGKey(0))
        self.engine_cfg = types.SimpleNamespace(
            local_steps=self.kw["local_steps"])

    def fit(self, key, rounds=None):
        self.state = self.state._replace(key=jnp.copy(key))
        self.state, r = reference.swarm_round(self.state, self.swarm,
                                              **self.kw)
        return [types.SimpleNamespace(
            train_loss=float(r.loss), mean_val_acc=float(jnp.mean(r.val_acc)),
            assignments=r.assignments)]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_planted_fault_reads_incorrect(fault, monkeypatch):
    ctx = make_ctx(7)
    drv = ctx.driver
    monkeypatch.setattr(drv, "build", state_unchanged(drv.build)
                        if fault == "state_unchanged" else HalfBatchTrainer)
    res = drv.run(ctx)
    assert res["attempted"] >= 1
    assert res["correct"] is False, res["checks"]
    if fault == "state_unchanged":
        # nothing moved: every leaf at least as large as the median leaf
        # reads a gap of 1, so the median leaf reads about 1
        assert all(c["value"] >= 0.5 for c in res["checks"].values())
