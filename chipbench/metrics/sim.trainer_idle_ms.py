"""sim.trainer_idle_ms: the device's idle time inside the trainer's
rounds, a round, in ms.

The first chip's idle time inside the window and inside the host spans
``bso.round`` that ``core/swarm.SwarmTrainer.round`` opens around each
round (its dispatch and the read of the round's log), over the traced
rounds (``chipbench/scopes.py``). Idle time outside those spans is the
caller's.
"""
from chipbench import scopes


def read(ctx):
    s = scopes.split(ctx)
    return None if s is None else s["trainer_idle_ms"]
