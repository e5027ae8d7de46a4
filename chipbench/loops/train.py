"""Full-batch GCN training through ``make_spmm_fn(compile_spmm(a, 1))``.

Traffic parameters (``traffic/<name>.json``):

* ``hidden``, ``layers``: the GCN's hidden width and depth (the
  configuration gives the features and classes);
* ``lr``: plain SGD's step size;
* ``first_steps``: the steps that set-up drives through the window's own
  compiled step and that the reference follows.

One jitted step, ``value_and_grad`` of ``repro.models.gnn.gcn_loss`` and the
SGD update, is compiled once and serves set-up and window alike; the loss
is read on the host after every step.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from chipbench import reference, work

FAULTS = ("half_batch", "answer")


class Loop:
    unit = "step"

    def __init__(self, run):
        from repro.core.api import compile_spmm, make_spmm_fn
        from repro.core.sparse import COOMatrix, csr_from_coo
        from repro.models import gnn

        if run.chips != 1:
            raise ValueError("the GCN loop runs on one chip")
        self.run = run
        self.graph = g = run.operand
        cfg, tr = run.config, run.traffic
        self.widths = ([int(cfg["num_features"])] + [int(tr["hidden"])] * (int(tr["layers"]) - 1)
                       + [int(cfg["num_classes"])])
        self.lr = float(tr["lr"])
        a = csr_from_coo(COOMatrix((g.n, g.n), g.row, g.col, g.val))
        with run.span("plan"):
            self.handle = compile_spmm(a, 1)
        self.replicated = NamedSharding(self.handle.mesh, PartitionSpec())
        spmm_fn = make_spmm_fn(self.handle)
        lr = self.lr

        def step(params, feats, labels):
            loss, grads = jax.value_and_grad(gnn.gcn_loss)(params, feats, labels, spmm_fn)
            return loss, jax.tree_util.tree_map(lambda w, dw: w - lr * dw, params, grads)

        self.step = jax.jit(step)
        self.work = work.gcn_step_work(g.nnz, g.n, self.widths)

    def counters(self) -> dict:
        return {"compiled_steps": self.step._cache_size()}

    def load(self, seed: int) -> None:
        """Make features, labels and weights on the device from ``seed``, then
        drive the first steps through the compiled step (the first compiles)."""
        n, widths = self.graph.n, self.widths

        def make(key):
            kx, ky, kw = jax.random.split(key, 3)
            feats = jax.random.normal(kx, (n, widths[0]), jnp.float32)
            labels = jax.random.randint(ky, (n,), 0, widths[-1])
            params = [{"w": jax.random.normal(k, (fi, fo), jnp.float32) * fi ** -0.5,
                       "b": jnp.zeros((fo,), jnp.float32)}
                      for k, fi, fo in zip(jax.random.split(kw, len(widths) - 1),
                                           widths[:-1], widths[1:])]
            return feats, labels, params

        self.feats, self.labels, self.params = jax.jit(
            make, out_shardings=self.replicated)(self.run.device_key(seed))
        self.params0 = jax.tree_util.tree_map(np.asarray, self.params)
        losses = []
        for i in range(int(self.run.traffic["first_steps"])):
            loss, self.params = self.step(self.params, self.feats, self.labels)
            losses.append(float(loss))
            if i == 0:
                params1 = jax.tree_util.tree_map(np.asarray, self.params)
        self.first = {"losses": losses, "params1": params1,
                      "params_last": jax.tree_util.tree_map(np.asarray, self.params)}

    def window(self, seconds: float, annotate) -> dict:
        step, params, feats, labels = self.step, self.params, self.feats, self.labels
        attempted = failed = 0
        lat = []
        t0 = time.perf_counter()
        t_end = t0
        while t_end - t0 < seconds:
            ts = t_end
            with annotate("step"):
                loss, params = step(params, feats, labels)
                loss = float(loss)
            t_end = time.perf_counter()
            lat.append(t_end - ts)
            attempted += 1
            failed += not np.isfinite(loss)
        self.params = params
        return {"window_s": t_end - t0, "attempted": attempted, "failed": failed,
                "latencies_s": lat}

    def release(self) -> None:
        self.handle = self.step = self.params = None

    def _reference(self, **kw) -> dict:
        return reference.gcn_steps(self.graph, self.params0, self.feats, self.labels,
                                   lr=self.lr, steps=len(self.first["losses"]), **kw)

    def readings(self) -> dict:
        """``loss_gap``, ``grad_gap``, ``change_gap`` of the first steps
        against the reference (``reference.train_gaps``)."""
        return reference.train_gaps(self.first, self._reference(), self.params0, lr=self.lr)

    def control_readings(self) -> dict:
        """The reference in bfloat16 in the program's place, and each fault
        planted in the reference, against the reference."""
        ref = self._reference()
        out = {}
        for name, kw in [("control", {"dtype": jnp.bfloat16})] + [
                (f, {"fault": f}) for f in FAULTS]:
            r = self._reference(**kw)
            prog = {"losses": r["losses"],
                    "params1": jax.tree_util.tree_map(lambda p, g: p - self.lr * g,
                                                      self.params0, r["first_grad"]),
                    "params_last": r["params"]}
            out[name] = reference.train_gaps(prog, ref, self.params0, lr=self.lr)
        return out
