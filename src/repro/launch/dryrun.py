import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
                           ).strip()

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

MUST be invoked as its own process (``python -m repro.launch.dryrun``): the
XLA_FLAGS line above executes before any other import — including jax —
because jax locks the device count on first init. Everything else in the
framework sees the single real CPU device.

Per combination this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs ShapeDtypeStruct params + inputs (zero allocation),
  3. jit-lowers the right step function with explicit in/out shardings,
  4. compiles, prints memory_analysis() and cost_analysis(),
  5. sums collective-op bytes from the optimized HLO for the roofline.

Exit code != 0 on any failure — a sharding mismatch or compile OOM here is
a bug in the framework, per the assignment.
"""

import argparse
import dataclasses
import functools
import json
import re
import sys

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.fl.round import make_train_step
from repro.launch import specs as S
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.models.model import Batch
from repro.sharding.rules import ShardingMode, param_pspecs

SDS = jax.ShapeDtypeStruct


# ---------------------------------------------------------------- helpers

def param_shape_tree(cfg: ModelConfig):
    """ShapeDtypeStructs of init_params without allocating."""
    return jax.eval_shape(
        functools.partial(M.init_params, cfg=cfg), jax.random.PRNGKey(0))


def with_shardings(tree, pspecs, mesh):
    def attach(x, s):
        if x is None:
            return None
        return SDS(x.shape, x.dtype,
                   sharding=NamedSharding(mesh, s if s is not None else P()))

    return jax.tree.map(attach, tree, pspecs, is_leaf=lambda x: x is None)


_COLL_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes per collective type from optimized HLO."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        op, dt, dims = m.group(1), m.group(2), m.group(3)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] = out.get(op, 0) + n * _DTYPE_BYTES[dt]
    return out


def modeled_link_bytes(coll: dict, n_participants: int) -> float:
    """Ring-collective traffic model per §Roofline (bytes on the busiest
    link direction per device)."""
    f = (n_participants - 1) / max(n_participants, 1)
    return (2 * f * coll.get("all-reduce", 0)
            + f * coll.get("all-gather", 0)
            + f * coll.get("reduce-scatter", 0)
            + f * coll.get("all-to-all", 0)
            + coll.get("collective-permute", 0))


# ------------------------------------------------------------- step builders

def build_train(cfg: ModelConfig, case, mesh, mode: ShardingMode,
                fl_clients: int, local_steps: int, gamma: float = 0.01,
                aggregation: str = "paper", remat: bool = False):
    """Single-pod: plain SGD step. Multi-pod: FL round across pods.

    aggregation: 'paper' (Alg.1 line 7, fp32 weighted param average) or
    'delta_bf16' (beyond-paper: bf16 delta aggregation, §Perf).
    remat: jax.checkpoint each layer-period scan body (memory-term knob).
    """
    pshapes = param_shape_tree(cfg)
    pspecs = param_pspecs(pshapes, mode, S.mesh_axis_sizes(mesh))
    if remat:
        cfg = dataclasses.replace(cfg, remat_layers=True)
    loss = functools.partial(M.loss_fn, cfg=cfg)

    if fl_clients:
        # batch leaves (pods, steps, B/pods, ...), q/sel (pods,)
        batch = S.batch_specs(cfg, case, client_dim=fl_clients)
        batch = Batch(
            tokens=SDS((fl_clients, local_steps) + batch.tokens.shape[1:],
                       jnp.int32),
            labels=SDS((fl_clients, local_steps) + batch.labels.shape[1:],
                       jnp.int32),
            media=SDS((fl_clients, local_steps) + batch.media.shape[1:],
                      batch.media.dtype) if batch.media is not None else None,
            frames=SDS((fl_clients, local_steps) + batch.frames.shape[1:],
                       batch.frames.dtype) if batch.frames is not None else None,
        )
        bspec_inner = S.batch_pspecs(
            S.batch_specs(cfg, case, client_dim=fl_clients), mesh,
            client_dim=True)

        def lift(sp):
            if sp is None:
                return None
            return P(sp[0], None, *tuple(sp)[1:])  # insert steps dim

        bspecs = jax.tree.map(lift, bspec_inner,
                              is_leaf=lambda x: x is None or isinstance(x, P))
        qspec = P()

        def step(params, batch, selected, q):
            # constrain per-client replicas onto the pod axis
            cspecs = jax.tree.map(lambda s: P("pod", *tuple(s)), pspecs)

            def lossb(p, b):
                return loss(p, b)

            n = q.shape[0]
            bparams = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), params)
            bparams = jax.lax.with_sharding_constraint(
                bparams, jax.tree.map(lambda s: NamedSharding(mesh, s),
                                      cspecs))
            from repro.fl.round import (delta_aggregate, local_sgd,
                                        weighted_aggregate)
            updated = jax.vmap(
                lambda p, b: local_sgd(lossb, p, b, gamma, local_steps))(
                    bparams, batch)
            if aggregation == "delta_bf16":
                return delta_aggregate(params, updated, selected, q)
            return weighted_aggregate(params, updated, selected, q)

        args = (with_shardings(pshapes, pspecs, mesh),
                with_shardings(batch, bspecs, mesh),
                SDS((fl_clients,), jnp.float32,
                    sharding=NamedSharding(mesh, P())),
                SDS((fl_clients,), jnp.float32,
                    sharding=NamedSharding(mesh, P())))
        out_specs = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
        return step, args, out_specs

    batch = S.batch_specs(cfg, case)
    bspecs = S.batch_pspecs(batch, mesh)
    train = make_train_step(loss, gamma)
    args = (with_shardings(pshapes, pspecs, mesh),
            with_shardings(batch, bspecs, mesh))
    out_specs = (jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
                 NamedSharding(mesh, P()))
    return train, args, out_specs


def build_prefill(cfg: ModelConfig, case, mesh, mode: ShardingMode):
    pshapes = param_shape_tree(cfg)
    pspecs = param_pspecs(pshapes, mode, S.mesh_axis_sizes(mesh))
    batch = S.batch_specs(cfg, case)
    bspecs = S.batch_pspecs(batch, mesh)

    def step(params, batch):
        return M.prefill(params, batch, cfg, cache_len=case.seq_len)

    # out shardings: logits + serve state (adaptive)
    state_shapes = jax.eval_shape(step, pshapes, batch)
    sspecs = S.serve_state_pspecs(state_shapes, cfg, mesh)
    args = (with_shardings(pshapes, pspecs, mesh),
            with_shardings(batch, bspecs, mesh))
    out_specs = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs)
    return step, args, out_specs


def build_decode(cfg: ModelConfig, case, mesh, mode: ShardingMode):
    pshapes = param_shape_tree(cfg)
    pspecs = param_pspecs(pshapes, mode, S.mesh_axis_sizes(mesh))
    b = case.global_batch
    cache_len = min(case.seq_len, cfg.sliding_window) if cfg.sliding_window \
        else case.seq_len

    # Build the serve-state structure via eval_shape of prefill on a short
    # prompt with the full cache length (cache size is set by cache_len).
    short = dataclasses.replace(case, seq_len=8)
    pb = S.batch_specs(cfg, short)
    pb = Batch(tokens=SDS((b, 8), jnp.int32), labels=None,
               media=SDS((b,) + pb.media.shape[1:], pb.media.dtype)
               if pb.media is not None else None,
               frames=SDS((b,) + pb.frames.shape[1:], pb.frames.dtype)
               if pb.frames is not None else None)

    def pre(params, batch):
        return M.prefill(params, batch, cfg, cache_len=cache_len)

    _, state_shapes = jax.eval_shape(pre, pshapes, pb)
    sspecs = S.serve_state_pspecs(state_shapes, cfg, mesh)

    def step(params, token, state):
        return M.decode_step(params, token, state, cfg)

    tok = SDS((b, 1), jnp.int32)
    tspec = S.token_pspec(b, mesh)
    args = (with_shardings(pshapes, pspecs, mesh),
            SDS(tok.shape, tok.dtype, sharding=NamedSharding(mesh, tspec)),
            with_shardings(state_shapes, sspecs, mesh))
    logits_spec = NamedSharding(mesh, tspec)
    out_specs = (logits_spec,
                 jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs))
    return step, args, out_specs


# ---------------------------------------------------------------- runner

def run_case(arch: str, shape: str, multi_pod: bool, *, debug_mesh=False,
             fl_local_steps: int = 1, fsdp: bool = True,
             dump_hlo: str = "", quiet: bool = False,
             exact_cost: bool = False, aggregation: str = "paper",
             remat: bool = False, ssd_chunk: int = 0,
             attn_bf16: bool = False) -> dict:
    cfg = get_config(arch)
    case = S.INPUT_SHAPES[shape]
    if case.name == "long_500k" and arch not in S.LONG_CONTEXT_ARCHS:
        rec = {"arch": arch, "shape": shape,
               "mesh": "multi" if multi_pod else "single",
               "status": "SKIP(full-attn)"}
        if not quiet:
            print(json.dumps(rec))
        return rec
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16",
                              scan_unroll=exact_cost,
                              attn_probs_bf16=attn_bf16)
    if ssd_chunk:
        cfg = dataclasses.replace(cfg, ssm_chunk=ssd_chunk)

    mesh = make_debug_mesh(multi_pod=multi_pod) if debug_mesh \
        else make_production_mesh(multi_pod=multi_pod)
    mode = ShardingMode(tensor_axis="model",
                        fsdp_axis="data" if fsdp else None)

    if case.kind == "train":
        fl_clients = mesh.devices.shape[0] if multi_pod else 0
        step, args, out_specs = build_train(cfg, case, mesh, mode,
                                            fl_clients, fl_local_steps,
                                            aggregation=aggregation,
                                            remat=remat)
    elif case.kind == "prefill":
        step, args, out_specs = build_prefill(cfg, case, mesh, mode)
    else:
        step, args, out_specs = build_decode(cfg, case, mesh, mode)

    with mesh:
        lowered = jax.jit(step, out_shardings=out_specs).lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    coll = collective_bytes(hlo)
    if dump_hlo:
        with open(dump_hlo, "w") as f:
            f.write(hlo)
    n_dev = mesh.devices.size
    result = {
        "arch": arch, "shape": shape,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "exact_cost": exact_cost,
        "variant": {"aggregation": aggregation, "remat": remat,
                    "ssd_chunk": ssd_chunk, "attn_bf16": attn_bf16},
        "status": "OK",
        "flops": cost.get("flops", -1.0) if cost else -1.0,
        "bytes_accessed": cost.get("bytes accessed", -1.0) if cost else -1.0,
        "collectives": coll,
        "collective_bytes_total": float(sum(coll.values())),
        "modeled_link_bytes": modeled_link_bytes(coll, n_dev),
        "n_devices": n_dev,
    }
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "generated_code_size_in_bytes"):
        result[attr] = getattr(mem, attr, None) if mem is not None else None
    if not quiet:
        print(json.dumps(result))
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry run")
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    choices=list(S.INPUT_SHAPES) + ["all"])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--debug-mesh", action="store_true",
                    help="use the tiny 8-device mesh (for tests)")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--local-steps", type=int, default=1,
                    help="FL local steps I in the multi-pod train step")
    ap.add_argument("--dump-hlo", default="")
    ap.add_argument("--exact-cost", action="store_true",
                    help="fully unroll internal scans so cost_analysis "
                         "counts true trip counts (slower compiles)")
    ap.add_argument("--probe-cost", action="store_true",
                    help="exact totals via k/2k-period linear probing "
                         "(fast; preferred over --exact-cost)")
    ap.add_argument("--aggregation", default="paper",
                    choices=["paper", "delta_bf16"])
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ssd-chunk", type=int, default=0)
    ap.add_argument("--attn-bf16", action="store_true")
    args = ap.parse_args(argv)

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(S.INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    if args.probe_cost:
                        probe_case(arch, shape, mp,
                                   debug_mesh=args.debug_mesh,
                                   fl_local_steps=args.local_steps,
                                   fsdp=not args.no_fsdp,
                                   aggregation=args.aggregation,
                                   remat=args.remat,
                                   ssd_chunk=args.ssd_chunk,
                                   attn_bf16=args.attn_bf16)
                    else:
                        run_case(arch, shape, mp, debug_mesh=args.debug_mesh,
                                 fl_local_steps=args.local_steps,
                                 fsdp=not args.no_fsdp, dump_hlo=args.dump_hlo,
                                 exact_cost=args.exact_cost,
                                 aggregation=args.aggregation, remat=args.remat,
                                 ssd_chunk=args.ssd_chunk,
                                 attn_bf16=args.attn_bf16)
                except Exception as e:  # noqa: BLE001 — report and fail
                    failures.append((arch, shape, mp, repr(e)))
                    print(json.dumps({"arch": arch, "shape": shape,
                                      "mesh": "multi" if mp else "single",
                                      "status": f"FAIL: {e!r}"}))
    if failures:
        sys.exit(1)


# ----------------------------------------------------------- probe mode

def _probe_cfg(cfg: ModelConfig, k_periods: int, k_enc: int) -> ModelConfig:
    """Shrink the stack to k periods (+ original prefix) and k_enc encoder
    layers, preserving the per-period layer pattern exactly."""
    _, period_specs, n_per = cfg.period_decomposition()
    plen = max(len(period_specs), 1)
    return dataclasses.replace(
        cfg,
        n_layers=cfg.n_dense_prefix + k_periods * plen,
        n_encoder_layers=k_enc if cfg.is_encoder_decoder else 0,
        encoder_seq=cfg.encoder_seq,
    )


def _case_costs(cfg, case, mesh, mode, fl_clients, local_steps,
                aggregation="paper", remat=False):
    if case.kind == "train":
        step, args, out_specs = build_train(cfg, case, mesh, mode,
                                            fl_clients, local_steps,
                                            aggregation=aggregation,
                                            remat=remat)
    elif case.kind == "prefill":
        step, args, out_specs = build_prefill(cfg, case, mesh, mode)
    else:
        step, args, out_specs = build_decode(cfg, case, mesh, mode)
    with mesh:
        compiled = jax.jit(step, out_shardings=out_specs).lower(*args).compile()
        cost = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll": coll}


def probe_case(arch: str, shape: str, multi_pod: bool, *, debug_mesh=False,
               fl_local_steps: int = 1, fsdp: bool = True,
               quiet: bool = False, aggregation: str = "paper",
               remat: bool = False, ssd_chunk: int = 0,
               attn_bf16: bool = False, no_fsdp_override: bool = False) -> dict:
    """Exact cost via linear extrapolation over HLO-identical periods.

    Compiles the model at k and 2k periods with every internal scan
    unrolled; per-period cost b = (c(2k)-c(k))/k and prefix cost
    a = c(k) - k b are exact because scan periods lower to identical HLO.
    Encoder-decoder archs get a third probe to separate the encoder slope.
    """
    cfg0 = get_config(arch)
    case = S.INPUT_SHAPES[shape]
    if case.name == "long_500k" and arch not in S.LONG_CONTEXT_ARCHS:
        rec = {"arch": arch, "shape": shape,
               "mesh": "multi" if multi_pod else "single",
               "status": "SKIP(full-attn)"}
        if not quiet:
            print(json.dumps(rec))
        return rec
    cfg0 = dataclasses.replace(cfg0, param_dtype="bfloat16",
                               scan_unroll=True,
                               attn_probs_bf16=attn_bf16)
    if ssd_chunk:
        cfg0 = dataclasses.replace(cfg0, ssm_chunk=ssd_chunk)
    mesh = make_debug_mesh(multi_pod=multi_pod) if debug_mesh \
        else make_production_mesh(multi_pod=multi_pod)
    mode = ShardingMode(tensor_axis="model",
                        fsdp_axis="data" if fsdp else None)
    fl_clients = mesh.devices.shape[0] if (multi_pod and
                                           case.kind == "train") else 0

    _, period_specs, n_per = cfg0.period_decomposition()
    n_enc = cfg0.n_encoder_layers
    k1, k2 = 1, 2
    e1 = 2 if cfg0.is_encoder_decoder else 0

    c1 = _case_costs(_probe_cfg(cfg0, k1, e1), case, mesh, mode, fl_clients,
                     fl_local_steps, aggregation, remat)
    c2 = _case_costs(_probe_cfg(cfg0, k2, e1), case, mesh, mode, fl_clients,
                     fl_local_steps, aggregation, remat)
    slope = {k: (c2[k] - c1[k]) / (k2 - k1) for k in ("flops", "bytes")}
    coll_slope = {op: (c2["coll"].get(op, 0) - c1["coll"].get(op, 0))
                  / (k2 - k1) for op in set(c1["coll"]) | set(c2["coll"])}

    enc_slope = {"flops": 0.0, "bytes": 0.0}
    enc_coll_slope = {}
    if cfg0.is_encoder_decoder:
        c3 = _case_costs(_probe_cfg(cfg0, k1, 2 * e1), case, mesh, mode,
                         fl_clients, fl_local_steps, aggregation, remat)
        enc_slope = {k: (c3[k] - c1[k]) / e1 for k in ("flops", "bytes")}
        enc_coll_slope = {op: (c3["coll"].get(op, 0) - c1["coll"].get(op, 0))
                          / e1 for op in set(c1["coll"]) | set(c3["coll"])}

    def total(key):
        base = c1[key] - k1 * slope[key] - e1 * enc_slope.get(key, 0.0)
        return base + n_per * slope[key] + n_enc * enc_slope.get(key, 0.0)

    coll_total = {}
    ops = set(c1["coll"]) | set(coll_slope) | set(enc_coll_slope)
    for op in ops:
        base = (c1["coll"].get(op, 0) - k1 * coll_slope.get(op, 0)
                - e1 * enc_coll_slope.get(op, 0))
        coll_total[op] = max(0.0, base + n_per * coll_slope.get(op, 0)
                             + n_enc * enc_coll_slope.get(op, 0))

    n_dev = mesh.devices.size
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "exact_cost": "probe",
        "variant": {"aggregation": aggregation, "remat": remat,
                    "ssd_chunk": ssd_chunk, "attn_bf16": attn_bf16,
                    "remat_layers": remat},
        "status": "OK",
        "flops": total("flops"),
        "bytes_accessed": total("bytes"),
        "collectives": coll_total,
        "collective_bytes_total": float(sum(coll_total.values())),
        "modeled_link_bytes": modeled_link_bytes(coll_total, n_dev),
        "n_devices": n_dev,
        "probe": {"k": [k1, k2], "n_periods": n_per,
                  "period_len": len(period_specs), "n_enc": n_enc},
    }
    if not quiet:
        print(json.dumps(rec))
    return rec




# ------------------------------------------------- seq-polynomial probing

def probe_case_seq(arch: str, shape: str, multi_pod: bool = False, *,
                   seqs=None, fsdp: bool = True, fl_local_steps: int = 1,
                   quiet: bool = False, aggregation: str = "paper",
                   remat: bool = False, ssd_chunk: int = 0) -> dict:
    """Exact cost via TWO linear probes: layer periods (k=1,2) and sequence
    length (polynomial <=2 in s; SSD chunk loops are linear in s, causal
    attention einsums exactly quadratic, embeddings/logits linear).

    Used for the SSD-family archs whose 32k-prefill chunk loops are too
    large to unroll directly: total(k,s) = A(s) + k*B(s) with A, B
    polynomials fitted from 2-3 small-seq compiles.
    """
    import numpy as np

    cfg0 = get_config(arch)
    case = S.INPUT_SHAPES[shape]
    cfg0 = dataclasses.replace(cfg0, param_dtype="bfloat16",
                               scan_unroll=True)
    if ssd_chunk:
        cfg0 = dataclasses.replace(cfg0, ssm_chunk=ssd_chunk)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mode = ShardingMode(tensor_axis="model",
                        fsdp_axis="data" if fsdp else None)
    fl_clients = mesh.devices.shape[0] if (multi_pod and
                                           case.kind == "train") else 0
    _, period_specs, n_per = cfg0.period_decomposition()
    has_attn = any(sp.mixer != "mamba" for sp in period_specs)
    if seqs is None:
        seqs = (1024, 2048, 4096) if has_attn else (1024, 2048)

    table = {}
    for k in (1, 2):
        ck = _probe_cfg(cfg0, k, 0)
        for sq in seqs:
            case_s = dataclasses.replace(case, seq_len=sq)
            table[(k, sq)] = _case_costs(ck, case_s, mesh, mode, fl_clients,
                                         fl_local_steps, aggregation, remat)

    deg = len(seqs) - 1
    target = case.seq_len

    def extrapolate(get):
        b_pts = [table[(2, sq)][get] - table[(1, sq)][get] if not callable(get)
                 else get(table[(2, sq)]) - get(table[(1, sq)]) for sq in seqs]
        a_pts = [(table[(1, sq)][get] if not callable(get)
                  else get(table[(1, sq)])) - b for sq, b in zip(seqs, b_pts)]
        bp = np.polyfit(seqs, b_pts, deg)
        ap = np.polyfit(seqs, a_pts, deg)
        return float(np.polyval(ap, target) + n_per * np.polyval(bp, target))

    ops = set()
    for c in table.values():
        ops |= set(c["coll"])
    coll_total = {op: max(0.0, extrapolate(
        lambda c, op=op: c["coll"].get(op, 0.0))) for op in ops}

    n_dev = mesh.devices.size
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "exact_cost": "probe-seq",
        "variant": {"aggregation": aggregation, "remat": remat,
                    "ssd_chunk": ssd_chunk},
        "status": "OK",
        "flops": max(0.0, extrapolate("flops")),
        "bytes_accessed": max(0.0, extrapolate("bytes")),
        "collectives": coll_total,
        "collective_bytes_total": float(sum(coll_total.values())),
        "modeled_link_bytes": modeled_link_bytes(coll_total, n_dev),
        "n_devices": n_dev,
        "probe": {"seqs": list(seqs), "n_periods": n_per, "target": target},
    }
    if not quiet:
        print(json.dumps(rec))
    return rec

if __name__ == "__main__":
    main()
