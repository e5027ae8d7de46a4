"""Served SpMM calls: one caller, back to back, through ``DistSpmm.__call__``.

Traffic parameters (``traffic/<name>.json``):

* ``operands``: how many seeded device-resident B operands the caller
  holds; call i uses operand i mod ``operands``;
* ``sample_calls``: how many calls of the window keep their C for the
  comparison, drawn from the seed by reservoir sampling over every call.

The configuration gives the operand and ``n_cols`` (N), and the cell its
chips: the handle is ``compile_spmm(a, 1)`` on one chip and
``compile_spmm(a, Topology.local(P))`` on P, with the default config.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from chipbench import reference, work



class Loop:
    unit = "call"

    def __init__(self, run):
        from repro.core.api import compile_spmm
        from repro.core.sparse import COOMatrix, csr_from_coo
        from repro.distributed.topology import Topology

        self.run = run
        self.graph = run.operand
        g = self.graph
        self.n_cols = int(run.config["n_cols"])
        a = csr_from_coo(COOMatrix((g.n, g.n), g.row, g.col, g.val))
        where = 1 if run.chips == 1 else Topology.local(run.chips)
        with run.span("plan"):
            self.handle = compile_spmm(a, where)
        h = self.handle
        self.sharding = NamedSharding(h.mesh, PartitionSpec(tuple(h.mesh.axis_names)))
        self.work = work.spmm_work(g.nnz, g.n, g.n, self.n_cols)
        self.kept = []

    def counters(self) -> dict:
        st = self.handle.stats()
        keys = ("strategy", "schedule_kind", "schedule_K", "overlap", "default_backend",
                "volume_rows", "volume_rows_padded", "P")
        return {k: st.get(k) for k in keys}

    def load(self, seed: int) -> None:
        """Make the seeded B operands on the device and warm every shape."""
        k = int(self.run.traffic["operands"])
        shape = (self.graph.n, self.n_cols)

        def make(key):
            return tuple(jax.random.normal(kk, shape, jnp.float32)
                         for kk in jax.random.split(key, k))

        self.bs = jax.jit(make, out_shardings=(self.sharding,) * k)(self.run.device_key(seed))
        self.seed = seed
        for b in self.bs:
            self.handle(b).block_until_ready()

    def window(self, seconds: float, annotate) -> dict:
        """Call until ``seconds`` have passed; keep a seeded sample of C."""
        h, bs = self.handle, self.bs
        want = int(self.run.traffic["sample_calls"])
        rng = np.random.default_rng([self.seed, 2])
        kept = []
        lat, failed = [], 0
        t0 = time.perf_counter()
        t_end = t0
        i = 0
        while t_end - t0 < seconds:
            which = i % len(bs)
            ts = time.perf_counter()
            try:
                with annotate("call"):
                    c = h(bs[which])
                    c.block_until_ready()
            except Exception as e:  # a failed call is counted, and the run goes on
                failed += 1
                self.run.say(f"call {i} failed: {type(e).__name__}: {e}")
                c = None
            t_end = time.perf_counter()
            lat.append(t_end - ts)
            if c is not None:
                if len(kept) < want:
                    kept.append((which, c))
                else:
                    j = int(rng.integers(0, i + 1))
                    if j < want:
                        kept[j] = (which, c)
            i += 1
        self.kept = kept
        return {"window_s": t_end - t0, "attempted": i, "failed": failed,
                "latencies_s": lat}

    def release(self) -> None:
        """Free the program's state; keep the sampled C on the host."""
        self.kept = [(which, np.asarray(c)) for which, c in self.kept]
        del self.handle
        self.handle = None

    def readings(self) -> dict:
        """``c_gap``: the worst kept C against the float32 segment sum."""
        refs = {w: reference.spmm(self.graph, self.bs[w]) for w in {w for w, _ in self.kept}}
        gaps = [reference.spmm_gap(np.asarray(c), refs[w]) for w, c in self.kept]
        return {"c_gap": max(gaps, default=float("inf"))}

    def control_readings(self) -> dict:
        """``c_gap`` with the bfloat16 segment sum in the program's place."""
        gaps = [reference.spmm_gap(reference.spmm_control(self.graph, b),
                                   reference.spmm(self.graph, b)) for b in self.bs]
        return {"control": {"c_gap": max(gaps)}}
