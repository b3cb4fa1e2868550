"""The system under test, built from a configuration file.

Everything the benchmark takes from the program is here: ``SearchServer``
over the corpus with the configuration's engine and engine settings,
``ServingRuntime`` in front of it with the configuration's
``OverloadPolicy``, and the runtime's admission error.  The server's
``query`` is wrapped so the benchmark logs each batch (host clock, batch
size) and marks it with a ``chipbench.query`` host span in a trace, which
carries the batch size.
"""
from __future__ import annotations

import math
import time

import jax
import numpy as np

from chipbench.tracing import BATCH_STAT, QUERY_SPAN


def _engine_cfg(cfg: dict, seed: int) -> dict:
    """The engine's settings with the file's spellings resolved: ``"inf"``
    is infinity, ``"$seed"`` the run's seed (below 2**31)."""
    out = {}
    for key, val in cfg.items():
        if val == "inf":
            val = math.inf
        elif val == "$seed":
            val = int(seed) % (2 ** 31)
        out[key] = val
    return out


class System:
    """A built server and runtime, and the three calls the load generator
    makes."""

    def __init__(self, config: dict, X, seed: int):
        from repro.launch.runtime import OverloadPolicy, Rejected, ServingRuntime
        from repro.launch.serve import FaultPolicy, SearchServer

        self.config = config
        self.k = int(config["k"])
        self.rejected = Rejected
        t0 = time.monotonic()
        self.server = SearchServer(
            X, engine=config["engine"],
            cfg=_engine_cfg(config["engine_cfg"], seed),
            policy=FaultPolicy(**config.get("fault_policy", {})))
        self.build_s = time.monotonic() - t0
        self.policy = OverloadPolicy(**config["policy"])
        self._runtime_cls = ServingRuntime
        self.runtime = None
        self.query_log: list = []  # (t_start, t_end, batch) per server.query
        inner = self.server.query

        def query(batch, *a, **kw):
            t = time.monotonic()
            with jax.profiler.TraceAnnotation(QUERY_SPAN,
                                              **{BATCH_STAT: len(batch)}):
                res = inner(batch, *a, **kw)
            self.query_log.append((t, time.monotonic(), len(batch)))
            return res

        self.server.query = query

    # ----------------------------------------------------------- set-up
    def warm(self, queries: np.ndarray, max_concurrency: int) -> list:
        """Compile and run once every batch bucket the traffic can form:
        powers of two from 8 up to the largest batch it can make."""
        from repro.core.scan import pow2ceil

        top = max(8, pow2ceil(min(self.policy.max_batch, max_concurrency)))
        buckets = []
        b = 8
        while b <= top:
            buckets.append(b)
            b *= 2
        for b in buckets:
            for _ in range(2):
                self.server.query(queries[:b], k=self.k,
                                  budget=self.policy.budget, record=False)
        self.query_log.clear()
        return buckets

    def build_stages(self) -> dict:
        """Seconds per build stage, where the engine records them."""
        hist = getattr(self.server.index, "train_history", None) or {}
        return dict(hist.get("build_s", {}))

    # ----------------------------------------------------------- serving
    def start(self) -> None:
        self.runtime = self._runtime_cls(self.server, self.policy).start()

    def stop(self) -> None:
        if self.runtime is not None:
            self.runtime.stop()

    def counters(self) -> dict:
        return dict(self.runtime.counters)

    def submit(self, q, deadline_ms: float):
        return self.runtime.submit(q, k=self.k, deadline_ms=deadline_ms)

    @staticmethod
    def on_done(ticket, fn) -> None:
        ticket._future.add_done_callback(lambda _f: fn())

    @staticmethod
    def result(ticket, timeout: float):
        return ticket.result(timeout=timeout)

    def close(self) -> None:
        """Drop the program's state, so the device memory it held is free
        for the reference."""
        self.stop()
        self.runtime = None
        self.server = None
