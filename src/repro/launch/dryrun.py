import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines ABOVE the docstring are load-bearing: jax locks the device
count at first init, so the 512 placeholder host devices must be forced
before ANY jax import. Nothing outside this module sets that flag.

Per cell this produces (EXPERIMENTS.md §Dry-run):
  * lowered + compiled artifacts for the production mesh(es):
    single-pod (16, 16) "data,model" and multi-pod (2, 16, 16)
    "pod,data,model";
  * compiled.memory_analysis() — proves the cell fits per-device HBM;
  * compiled.cost_analysis() + HLO collective-byte parse — the inputs to
    the §Roofline terms.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.json
  python -m repro.launch.dryrun --all --multi-pod
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, get_config
from ..distributed.context import make_context
from ..distributed.sharding import (
    as_shardings, batch_specs, cache_specs, opt_state_specs, param_specs,
)
from ..models.transformer import DecodeCache, decode_step
from ..optim.adamw import AdamWConfig
from ..train.steps import make_prefill_step, make_train_step
from .hlo_analysis import collective_bytes, roofline
from .mesh import make_production_mesh
from .specs import (
    SHAPES, abstract_cache, abstract_opt_state, abstract_params,
    cell_status, input_specs,
)

__all__ = ["run_cell", "main"]


def _mem_dict(mem) -> Dict[str, float]:
    if mem is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = float(v)
    return out


def _attention_correction(cfg, shape, mult: float) -> Dict[str, float]:
    """Analytic add-back for flash-attention inner scans (GLOBAL totals).

    XLA cost_analysis counts while bodies once; the layer dimension is
    recovered by the unrolled probes, but flash attention's q/kv chunk
    scans remain. Those flops/bytes are exact closed forms; anything with
    query length < 1024 takes the dense (fully counted) path and needs no
    correction. ``mult``: 1 forward-only, 3 fwd+bwd (probes use
    remat=False). SSM chunk-scan undercount is ~1.5% of the mamba matmul
    flops and is documented, not corrected (EXPERIMENTS.md §Roofline).
    """
    b, s = shape.global_batch, shape.seq_len
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    apps = []  # (q_len, kv_len, count)
    if cfg.family in ("dense", "moe", "vlm"):
        s_tok = s  # vlm prefix counts toward the seq budget
        apps.append((s_tok, s_tok, cfg.n_layers))
    elif cfg.family == "audio":
        apps.append((s, s, cfg.n_layers))
    elif cfg.family == "encdec":
        e = cfg.frontend_len
        apps.append((e, e, cfg.n_enc_layers))
        apps.append((s, s, cfg.n_layers))
        apps.append((s, e, cfg.n_layers))
    elif cfg.family == "hybrid":
        apps.append((s, s, cfg.n_layers // max(cfg.attn_every, 1)))
    flops = bytes_ = 0.0
    qc, kc = 512, 1024
    for q, kv, n in apps:
        if q < 1024:
            continue  # dense path — fully counted by the probes
        f = 4.0 * b * q * kv * h * hd
        nq = max(q // qc, 1)
        by = b * (nq * kv * kvh * hd * 2 * 2 + q * h * hd * 4 * 2)
        flops += n * f * mult
        bytes_ += n * by * mult
    return {"flops": flops, "bytes": bytes_}


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             opt_overrides: Optional[dict] = None,
             probes: bool = True) -> Dict[str, Any]:
    """Lower + compile one cell; returns the §Dry-run record."""
    cfg = get_config(arch)
    if opt_overrides:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **opt_overrides)
    shape = SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": shape.mode,
        # records from different jax versions compile different HLO; tag
        # them so §Roofline comparisons never mix compiler generations
        "jax": jax.__version__,
    }
    status = cell_status(cfg, shape)
    rec["status"] = status
    if status != "run":
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    # the dry-run fleet through the same substrate naming as serving:
    # records carry the Topology so §Roofline rows are attributable
    from ..distributed.topology import Topology

    rec["topology"] = Topology.from_mesh(mesh).describe()
    dist = make_context(mesh, fsdp=cfg.fsdp)
    rec.update(_compile_one(cfg, shape, mesh, dist, t0, chips))
    rec["params"] = cfg.params_count()
    rec["active_params"] = cfg.active_params_count()
    rec["chips"] = chips

    if probes and rec.get("status") == "run":
        try:
            rec["roofline_corrected"] = _probe_corrected(
                cfg, shape, mesh, dist, chips, rec)
        except Exception as e:
            rec["probe_error"] = f"{type(e).__name__}: {e}"[:500]
    return rec


def _units(cfg) -> int:
    """Linear depth units for probe extrapolation."""
    if cfg.family == "hybrid":
        return cfg.n_layers // max(cfg.attn_every, 1)
    return cfg.n_layers


def _probe_cfg(cfg, units: int):
    import dataclasses as _dc
    kw = dict(scan_layers=False, remat=False)
    if cfg.family == "hybrid":
        kw["n_layers"] = units * cfg.attn_every
    else:
        kw["n_layers"] = units
        if cfg.family == "encdec":
            kw["n_enc_layers"] = units
    return _dc.replace(cfg, **kw)


def _probe_corrected(cfg, shape, mesh, dist, chips, rec_full):
    """Depth-exact roofline: two unrolled shallow probes + flash add-back."""
    u_full = _units(cfg)
    res = {}
    for u in (1, 2):
        pr = _compile_one(_probe_cfg(cfg, u), shape, mesh, dist,
                          time.time(), chips)
        if pr.get("status") != "run":
            raise RuntimeError(pr.get("error", "probe failed"))
        res[u] = pr

    def lin(key, sub=None):
        v1 = res[1][key][sub] if sub else res[1][key]
        v2 = res[2][key][sub] if sub else res[2][key]
        v1, v2 = float(v1 or 0), float(v2 or 0)
        return v1 + (u_full - 1) * (v2 - v1)

    flops = lin("cost", "flops")
    bytes_acc = lin("cost", "bytes accessed")
    coll = lin("collectives", "total")
    mult = 3.0 if shape.mode == "train" else 1.0
    # decode runs single-query (dense-path) attention — no flash scans,
    # fully counted by the probes, NO analytic add-back (the cache length
    # is not a query length!).
    if shape.mode == "decode":
        corr = {"flops": 0.0, "bytes": 0.0}
    else:
        corr = _attention_correction(cfg, shape, mult)
    flops += corr["flops"] / chips
    bytes_acc += corr["bytes"] / chips
    model_flops = rec_full["roofline"].get("model_flops")
    out = roofline({"flops": flops, "bytes accessed": bytes_acc},
                   {"total": coll}, chips=chips, model_flops=model_flops)
    out["attention_correction_flops_per_chip"] = corr["flops"] / chips
    out["probe_units"] = u_full
    return out


def _compile_one(cfg, shape, mesh, dist, t0, chips) -> Dict[str, Any]:
    rec: Dict[str, Any] = {"status": "run"}
    params_sds = abstract_params(cfg)
    pspecs = param_specs(params_sds, cfg, dist)
    pshard = as_shardings(pspecs, dist)
    b, s = shape.global_batch, shape.seq_len

    if shape.mode == "train":
        opt_sds = abstract_opt_state(cfg)
        oshard = as_shardings(opt_state_specs(pspecs), dist)
        bspecs = batch_specs(cfg, dist, b)
        batch_sds = input_specs(cfg, shape)
        bshard = {k: NamedSharding(mesh, bspecs[k]) for k in batch_sds}
        step = make_train_step(cfg, dist, AdamWConfig())
        jitted = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_sds, opt_sds, batch_sds)
        tokens = b * s
        model_flops = 6.0 * cfg.active_params_count() * tokens
    elif shape.mode == "prefill":
        bspecs = batch_specs(cfg, dist, b)
        batch_sds = input_specs(cfg, shape)
        bshard = {k: NamedSharding(mesh, bspecs[k]) for k in batch_sds}
        step = make_prefill_step(cfg, dist)
        jitted = jax.jit(step, in_shardings=(pshard, bshard))
        lowered = jitted.lower(params_sds, batch_sds)
        model_flops = 2.0 * cfg.active_params_count() * b * s
    else:  # decode
        # cache length: +16 keeps it divisible by the model axis size so
        # the kv_seq_shard (flash-decoding) layout can shard dim 3.
        cache_sds = abstract_cache(cfg, b, s + 16)
        cspec_dict = cache_specs(cfg, dist, b)
        cshard = DecodeCache(**{
            f: (NamedSharding(mesh, cspec_dict[f])
                if getattr(cache_sds, f) is not None and f in cspec_dict
                else None)
            for f in ("k", "v", "ssm_h", "ssm_conv", "shared_k",
                      "shared_v", "cross_k", "cross_v", "length")})
        tok_sds = input_specs(cfg, shape)["token"]
        tok_shard = NamedSharding(mesh, P(
            dist.batch_axes if b % dist.batch_size_divisor == 0 else None,
            None))
        if cfg.family == "encdec":
            enc_sds = jax.ShapeDtypeStruct(
                (b, cfg.frontend_len, cfg.d_model), jnp.dtype(cfg.dtype))
            enc_shard = NamedSharding(mesh, P(
                dist.batch_axes if b % dist.batch_size_divisor == 0 else None,
                None, None))

            def step(params, token, cache, enc_out):
                return decode_step(params, cfg, dist, token, cache, enc_out)

            jitted = jax.jit(step,
                             in_shardings=(pshard, tok_shard, cshard, enc_shard),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_sds, tok_sds, cache_sds, enc_sds)
        else:
            def step(params, token, cache):
                return decode_step(params, cfg, dist, token, cache)

            jitted = jax.jit(step, in_shardings=(pshard, tok_shard, cshard),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_sds, tok_sds, cache_sds)
        model_flops = 2.0 * cfg.active_params_count() * b

    rec["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    rec["memory"] = _mem_dict(compiled.memory_analysis())
    rec["cost"] = {k: float(v) for k, v in cost.items()
                   if isinstance(v, (int, float)) and k in
                   ("flops", "bytes accessed", "transcendentals",
                    "utilization", "bytes accessed output")}
    rec["collectives"] = coll
    rec["roofline"] = roofline(rec["cost"], coll, chips=chips,
                               model_flops=model_flops)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="SHIRO multi-pod dry-run")
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) cell on the chosen mesh")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip the roofline probe compiles (multi-pod pass)")
    args = ap.parse_args()

    cells = ([(a, sh) for a in ARCHS for sh in SHAPES]
             if args.all else [(args.arch, args.shape)])
    if not args.all and (args.arch is None or args.shape is None):
        ap.error("--arch and --shape required unless --all")

    for arch, shape_name in cells:
        try:
            rec = run_cell(arch, shape_name, multi_pod=args.multi_pod,
                           probes=not args.no_probes)
        except Exception as e:  # record failures; the suite must be green
            rec = {"arch": arch, "shape": shape_name,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "status": f"FAIL({type(e).__name__})",
                   "error": str(e)[:2000],
                   "traceback": traceback.format_exc()[-4000:]}
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
