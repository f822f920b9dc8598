"""EngineFleet: scale the serving engine *out* to N routed replicas.

One ``ServingEngine`` scales up (continuous batching, paged KV, and —
with a mesh — tensor parallelism over ``tp`` devices).  The fleet scales
out: N engine replicas, each wrapped in a ``LocalEngineBackend`` and put
behind one ``repro.dispatch.Dispatcher``, so PopPy's fan-out traffic
spreads across replicas with no client-side changes (the dispatcher *is*
a ``Backend``).

Device carving: replica ``i`` takes the ``tp`` devices starting at
``i * tp`` when the host has that many, so fleet replicas run on disjoint
meshes — at ``tp=1`` too, where each replica's mesh is its one device
(four replicas on a four-chip host hold one chip each).  When the host
is too small the replicas share the first ``tp`` devices — on a
single-process simulation they time-share anyway, and scheduling (slots,
queues, page pools) is still fully per-replica.

Routing: the default ``prefix_affinity`` policy probes each replica's
radix prefix cache (``LocalEngineBackend.prefix_probe``) and sends a
request to the replica already holding the longest prefix of its prompt,
falling back to least-outstanding for cold traffic (DESIGN.md §3.4).
"""

from __future__ import annotations

import asyncio

import jax

from repro.dispatch import Dispatcher
from repro.launch.mesh import make_serving_mesh
from repro.serving.backend import LocalEngineBackend
from repro.serving.engine import ServingEngine


class EngineFleet:
    """N serving-engine replicas behind a prefix-affinity router.

    ``replicas`` engines are built from one ``(model, params)`` pair;
    each replica places its own copy on its own
    ``make_serving_mesh(tp)``, a disjoint device slice when the host has
    ``replicas * tp`` devices.  Remaining keyword arguments go to
    every ``ServingEngine``; ``dispatcher_kwargs`` (e.g. ``cache=``,
    ``hedge=``) go to the fleet's ``Dispatcher``.
    """

    def __init__(self, model, params, *, replicas: int = 1, tp: int = 1,
                 policy: str = "prefix_affinity", tokenizer=None,
                 hedge_timeout=None, dispatcher_kwargs: dict | None = None,
                 **engine_kwargs):
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        devices = jax.devices()
        if tp > len(devices):
            raise RuntimeError(
                f"tp={tp} needs {tp} devices, have {len(devices)}")
        self.replicas = replicas
        self.tp = tp
        self.names = [f"replica{i}" for i in range(replicas)]
        self.engines: list[ServingEngine] = []
        for i, name in enumerate(self.names):
            lo = i * tp
            sl = devices[lo:lo + tp] if lo + tp <= len(devices) \
                else devices[:tp]
            self.engines.append(ServingEngine(
                model, params, mesh=make_serving_mesh(tp, devices=sl),
                name=name, **engine_kwargs))
        self.backends = [
            LocalEngineBackend(e, tokenizer, hedge_timeout=hedge_timeout)
            for e in self.engines]
        self.dispatcher = Dispatcher(
            self.backends, policy=policy, names=self.names,
            **(dispatcher_kwargs or {}))

    @property
    def stats(self):
        """The fleet dispatcher's ``DispatchStats`` — per-replica routed /
        prefix-hit counters live under ``snapshot()["backends"]``."""
        return self.dispatcher.stats

    def engine_stats(self) -> dict:
        return {name: e.stats()
                for name, e in zip(self.names, self.engines)}

    async def stop(self):
        await asyncio.gather(*(e.stop() for e in self.engines))
