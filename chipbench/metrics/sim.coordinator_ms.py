"""sim.coordinator_ms: the coordinator's device time a round, in ms.

The self time of every op the round program runs under the scopes of
the coordinator's four layers: ``bso.stat_upload``
(``core/diststats.swarm_distribution_matrix``), ``bso.kmeans``
(``core/kmeans.kmeans``), ``bso.brain_storm``
(``core/bso.brain_storm_jax``) and ``bso.eq2``
(``core/aggregation.cluster_fedavg``), from the device trace, over the
traced rounds (``chipbench/scopes.py``, which prints each apart).
"""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, *scopes.COORDINATOR)
