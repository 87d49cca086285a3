"""The trace marks of the BSO-SL round: one ``jax.named_scope`` per
layer in every program that runs the layer functions, and the host
spans of ``SwarmTrainer``'s round path, as a profiler trace sees them.

The scopes are HLO metadata only: the parity tests elsewhere pin that
the compiled arithmetic is unchanged (``test_sweep``'s fit against
fit_scanned, ``test_engine``'s scan against round-wise calls)."""
import glob
import os
import re
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import OptimizerConfig, SwarmConfig
from repro.core.engine import jit_run_rounds, jit_swarm_round
from repro.core.swarm import SwarmTrainer
from repro.data.dr import TABLE_I, make_dr_swarm_data
from repro.models import build_model

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from chipbench import scopes  # noqa: E402

SMALL_TABLE = np.maximum(TABLE_I // 16, (TABLE_I > 0).astype(np.int64) * 2)
LOCAL_STEPS = 2


@pytest.fixture(scope="module")
def trainer():
    clients = make_dr_swarm_data(image_size=16, seed=0, table=SMALL_TABLE)
    swarm = SwarmConfig(n_clients=len(clients), n_clusters=3, rounds=2,
                        local_steps=LOCAL_STEPS, kmeans_iters=10)
    return SwarmTrainer(build_model(get_config("squeezenet-dr")), clients,
                        swarm, OptimizerConfig(name="adam", lr=2e-3),
                        jax.random.PRNGKey(0), batch_size=8,
                        aggregation="bso")


def local_scan_whiles(hlo_text: str) -> list:
    """Names of the ``while`` ops whose body computation itself holds
    the backward pass (``transpose(jvp(...))`` ops): the local scan."""
    bodies, comp = {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = bodies.setdefault(
                line.split()[1 if line.startswith("ENTRY") else 0]
                .lstrip("%"), [])
        elif comp is not None:
            comp.append(line)
    whiles = re.findall(r"^\s+(?:ROOT )?%(\S+) = .* while\(.*?body=%?([\w.\-]+)",
                        hlo_text, re.M)
    return [w for w, body in whiles
            if any("transpose(jvp(" in ln for ln in bodies.get(body, ()))]


@pytest.mark.parametrize("program", ["swarm_round", "run_rounds"])
def test_compiled_round_carries_the_six_scopes(trainer, program):
    t = trainer
    if program == "swarm_round":
        lowered = jit_swarm_round.lower(t.state, t.swarm_data, t.engine_cfg)
    else:
        lowered = jit_run_rounds.lower(t.state, t.swarm_data, t.engine_cfg, 2)
    text = lowered.compile().as_text()
    for scope in scopes.SCOPES:
        assert f"/{scope}/" in text, scope
    whiles = local_scan_whiles(text)
    assert len(whiles) == 1, whiles
    assert scopes.op_scopes(text)[whiles[0]] == "bso.local_phase"


def test_fit_rounds_are_host_spans(trainer, tmp_path):
    start = len(trainer.history)
    with jax.profiler.trace(str(tmp_path)):
        trainer.fit(jax.random.PRNGKey(2), rounds=2)
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    host = [e for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines
            for e in line.events]
    rounds = sorted((e for e in host if e.name == "bso.round"),
                    key=lambda e: e.start_ns)
    assert [dict(e.stats)["step_num"] for e in rounds] == [start, start + 1]
    # the static eval row counts of the layout, on every round
    real = sum(len(c["val"][1]) for c in trainer.data)
    slots = sum(int(np.prod(v["labels"].shape[:3]))
                for v in trainer.swarm_data.val)
    assert 0 < real <= slots
    for r in rounds:
        stats = dict(r.stats)
        assert (stats["eval_rows"], stats["eval_real_rows"]) == (slots, real)
        inside = [e.name for e in host
                  if r.start_ns <= e.start_ns
                  and e.start_ns + e.duration_ns <= r.start_ns + r.duration_ns]
        assert inside.count("bso.dispatch") == 1
        assert inside.count("bso.round_log") == 1
