import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

For every (architecture x input shape x mesh) cell:
  jax.jit(step).lower(**ShapeDtypeStruct stand-ins).compile()
must succeed on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh.
We record memory_analysis() (fits 16 GiB/chip?), cost_analysis() FLOPs/bytes,
and the collective schedule parsed from the compiled HLO.

Loop-corrected costs: the only ``while`` loops in any step are the layer
scans; per-layer probe programs (same shardings, same remat) are compiled
separately and combined as
    corrected = full_raw + sum_kind (trips - instances) * probe_kind
(see DESIGN.md §7 and launch/hlo_analysis.py).

Usage:
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  python -m repro.launch.dryrun --all            # resumable sweep
  python -m repro.launch.dryrun --all --multi-pod
"""

import argparse
import gc
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ASSIGNED, SHAPES, batch_specs, cache_specs, get
from ..core.perf_model import TPU_V5E, roofline
from ..core.plan import ShardingPlan
from ..models import params as pp
from ..models.lm import LM, apply_block, block_defs, _cache_struct
from ..optim.schedules import cosine_warmup
from ..runtime.steps import (make_decode_step, make_prefill_step,
                             make_train_step, state_structs)
from .hlo_analysis import count_kinds, parse_collectives, total_link_bytes
from .mesh import make_production_mesh

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


# ---------------------------------------------------------------------------
def _sds(shape, dtype, plan, axes):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=plan.sharding_for(axes, shape))


def _compile_and_analyze(fn, args, n_dev, pod_stride, loop_corr=None,
                         donate=()):
    t0 = time.time()
    lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
    compiled = lowered.compile()
    compile_s = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    colls = parse_collectives(txt, n_dev, pod_stride)
    rec = {
        "compile_s": round(compile_s, 2),
        "mem": {
            "argument_gib": ma.argument_size_in_bytes / 2**30,
            "output_gib": ma.output_size_in_bytes / 2**30,
            "temp_gib": ma.temp_size_in_bytes / 2**30,
            "peak_gib": (ma.argument_size_in_bytes
                         + ma.temp_size_in_bytes) / 2**30,
        },
        "flops_raw": float(ca.get("flops", 0.0)),
        "bytes_raw": float(ca.get("bytes accessed", 0.0)),
        "collectives_raw": count_kinds(colls),
        "coll_ici_raw": total_link_bytes(colls)[0],
        "coll_dci_raw": total_link_bytes(colls)[1],
        "top_collectives": sorted(
            colls, key=lambda c: -c["link_bytes"])[:8],
    }
    if os.environ.get("REPRO_SAVE_HLO"):
        rec["_hlo_text"] = txt
    return rec, compiled


def _probe(cfg, plan, kind, mode, B, S):
    """Compile a single-block probe with production shardings; returns raw
    per-layer (flops, bytes, ici, dci)."""
    n_dev = plan.mesh.devices.size
    pod_stride = 256 if "pod" in plan.mesh.axis_names else 0
    pdefs = block_defs(kind, cfg, None)
    pl_structs = pp.shape_structs(pdefs, plan)
    Sx = 1 if mode == "decode" else S
    x = _sds((B, Sx, cfg.d_model), jnp.bfloat16, plan,
             ("batch", "sp" if mode != "decode" else None, None))

    extra = {}
    if kind == "dec":
        enc_len = cfg.enc_len if mode == "decode" else max(32, S)
        extra["enc_out"] = _sds((B, cfg.enc_len if mode == "decode" else S,
                                 cfg.d_model), jnp.bfloat16, plan,
                                ("batch", "sp", None))

    cache_arg = None
    if mode == "decode":
        # a one-layer stack: decode blocks write into a stack at ``layer``
        cs = _cache_struct(kind, cfg, B, cfg.cache_len or S, 1)
        def leaf(t):
            shape, dtype, axes = t
            return _sds(tuple(shape), dtype, plan, tuple(axes))
        cache_arg = jax.tree.map(
            leaf, cs, is_leaf=lambda t: isinstance(t, tuple) and len(t) == 3
            and isinstance(t[0], tuple))

    mp = None
    if cfg.mrope:
        mp = _sds((3, B, Sx), jnp.int32, plan, (None, "batch", None))

    def positions(S_):
        return jnp.broadcast_to(jnp.arange(S_)[None], (B, S_))

    if mode == "train":
        def probe(x, pl, mrope=None, enc_out=None):
            def f(x, pl):
                y, _, aux = apply_block(kind, x, pl, cfg, plan, mode="train",
                                        positions=positions(Sx),
                                        mrope_positions=mrope,
                                        enc_out=enc_out)
                s = jnp.sum(y.astype(jnp.float32))
                for v in (aux or {}).values():
                    s = s + jnp.sum(v)
                return s
            g = jax.grad(jax.checkpoint(f), argnums=(0, 1))(x, pl)
            return g
        args = [x, pl_structs]
        if cfg.mrope:
            probe_fn = lambda x, pl, mp: probe(x, pl, mrope=mp)
            args.append(mp)
        elif kind == "dec":
            probe_fn = lambda x, pl, eo: probe(x, pl, enc_out=eo)
            args.append(extra["enc_out"])
        else:
            probe_fn = lambda x, pl: probe(x, pl)
    elif mode == "prefill":
        def probe_fn(x, pl, *rest):
            mrope = rest[0] if cfg.mrope else None
            enc_out = rest[0] if (kind == "dec" and not cfg.mrope) else None
            return apply_block(kind, x, pl, cfg, plan, mode="prefill",
                               cache="init", positions=positions(Sx),
                               mrope_positions=mrope, enc_out=enc_out)[:2]
        args = [x, pl_structs]
        if cfg.mrope:
            args.append(mp)
        elif kind == "dec":
            args.append(extra["enc_out"])
    else:
        pos = _sds((B, 1), jnp.int32, plan, ("batch", None))
        def probe_fn(x, pl, cache, pos_, *rest):
            mrope = rest[0] if cfg.mrope else None
            return apply_block(kind, x, pl, cfg, plan, mode="decode",
                               cache=cache, positions=pos_, pos_offset=0,
                               mrope_positions=mrope, layer=0)[:2]
        args = [x, pl_structs, cache_arg, pos]
        if cfg.mrope:
            args.append(mp)

    rec, _ = _compile_and_analyze(probe_fn, args, n_dev, pod_stride)
    return rec


def _probe_micro(cfg, plan, shape, B_micro):
    """Compile one microbatch's value_and_grad(loss) with production
    shardings — the grad-accumulation body for two-level loop correction."""
    from ..models.lm import LM
    n_dev = plan.mesh.devices.size
    pod_stride = 256 if "pod" in plan.mesh.axis_names else 0
    model = LM(cfg)
    pstructs = pp.shape_structs(model.param_defs(), plan)
    batch = batch_specs(cfg, shape, plan, batch=B_micro)

    def micro(params, b):
        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss(p, b, plan), has_aux=True)(params)
        return loss, grads

    rec, _ = _compile_and_analyze(micro, (pstructs, batch), n_dev,
                                  pod_stride)
    return rec


# ---------------------------------------------------------------------------
def run_cell(arch: str, shape: str, multi_pod: bool = False,
             plan_overrides=None, tag: str = "", verbose: bool = True,
             cfg_overrides=None):
    import dataclasses
    cfg = get(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    sh = SHAPES[shape]
    mode = sh["mode"]
    if not cfg.supports(shape):
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "skipped": True, "reason": cfg.skip_reason(shape)}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    pod_stride = 256 if multi_pod else 0
    plan = ShardingPlan(mesh=mesh)
    if plan_overrides:
        for k, v in plan_overrides.items():
            setattr(plan, k, v)

    B, S = sh["batch"], sh["seq"]
    # keep the unrolled attention q/kv block loops bounded (compile time
    # scales with unrolled block count; VMEM-sized tiles stay the kernel's
    # job — see kernels/flash_attention.py)
    if mode != "decode" and S >= 32768:
        cfg.q_block = max(cfg.q_block, S // 8)
        cfg.kv_block = max(cfg.kv_block, S // 8)
    result = {"arch": arch, "shape": shape, "mesh": "2x16x16" if multi_pod
              else "16x16", "multi_pod": multi_pod, "mode": mode, "tag": tag,
              "batch": B, "seq": S, "chips": n_dev}
    t_start = time.time()
    try:
        if mode == "train":
            step = make_train_step(cfg, plan, cosine_warmup(3e-4, 100, 10000))
            state = state_structs(cfg, plan)
            batch = batch_specs(cfg, shape, plan)
            rec, compiled = _compile_and_analyze(
                step, (state, batch), n_dev, pod_stride, donate=(0,))
        elif mode == "prefill":
            model = LM(cfg)
            pstructs = pp.shape_structs(model.param_defs(), plan)
            step = make_prefill_step(cfg, plan, cache_len=S)
            batch = batch_specs(cfg, shape, plan)
            rec, compiled = _compile_and_analyze(
                step, (pstructs, batch), n_dev, pod_stride)
        else:
            model = LM(cfg)
            pstructs = pp.shape_structs(model.param_defs(), plan)
            caches = cache_specs(cfg, B, S, plan)
            step = make_decode_step(cfg, plan, cache_len=S)
            batch = batch_specs(cfg, shape, plan)
            rec, compiled = _compile_and_analyze(
                step, (pstructs, caches, batch), n_dev, pod_stride,
                donate=(1,))
        if "_hlo_text" in rec:
            try:
                import zstandard as zstd
                hdir = RESULTS_DIR / "hlo"
                hdir.mkdir(parents=True, exist_ok=True)
                hp = hdir / (f"{arch}__{shape}__"
                             f"{'mp' if multi_pod else 'sp'}"
                             f"{('__' + tag) if tag else ''}.hlo.zst")
                hp.write_bytes(zstd.ZstdCompressor(level=9).compress(
                    rec.pop("_hlo_text").encode()))
                result["hlo_path"] = str(hp)
            except Exception:   # noqa: BLE001
                rec.pop("_hlo_text", None)
        result.update(rec)
        del compiled
    except Exception as e:  # noqa: BLE001
        result["ok"] = False
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-3000:]
        return result

    # --- loop-corrected totals -------------------------------------------------
    model = LM(cfg)
    if mode in ("prefill", "decode"):
        cfg.cache_len = (min(S, cfg.window) if cfg.attn_kind == "swa" else S)
    loop_specs = model.loop_specs("decode" if mode == "decode" else mode)
    n_micro = cfg.n_microbatches if mode == "train" else 1
    B_micro = B // n_micro
    flops = result["flops_raw"]
    byts = result["bytes_raw"]
    ici = result["coll_ici_raw"]
    dci = result["coll_dci_raw"]
    probes = {}
    dec_len = max(32, S // 8) if cfg.family == "encdec" else S

    def layer_corrections(base):
        """sum over kinds of (trips - instances) * per-layer probe costs."""
        f = b = i = d = 0.0
        for kind, trips, instances in loop_specs:
            if trips <= instances:
                continue
            try:
                S_probe = dec_len if kind == "dec" else S
                prec = _probe(cfg, plan, kind, mode, B_micro, S_probe)
            except Exception as e:  # noqa: BLE001
                result["probe_error_" + kind] = f"{type(e).__name__}: {e}"
                continue
            probes[kind] = prec
            k = trips - instances
            f += k * prec["flops_raw"]
            b += k * prec["bytes_raw"]
            i += k * prec["coll_ici_raw"]
            d += k * prec["coll_dci_raw"]
        return f, b, i, d

    cf, cb, ci_, cd = layer_corrections(result)
    if n_micro > 1:
        # full = outside + 1x micro_body(raw); true = outside + n*micro_true
        # -> probe one microbatch's value_and_grad with identical shardings
        try:
            mp_rec = _probe_micro(cfg, plan, shape, B_micro)
            probes["_micro"] = mp_rec
            micro_true = {
                "flops": mp_rec["flops_raw"] + cf,
                "bytes": mp_rec["bytes_raw"] + cb,
                "ici": mp_rec["coll_ici_raw"] + ci_,
                "dci": mp_rec["coll_dci_raw"] + cd,
            }
            flops = flops - mp_rec["flops_raw"] + n_micro * micro_true["flops"]
            byts = byts - mp_rec["bytes_raw"] + n_micro * micro_true["bytes"]
            ici = ici - mp_rec["coll_ici_raw"] + n_micro * micro_true["ici"]
            dci = dci - mp_rec["coll_dci_raw"] + n_micro * micro_true["dci"]
        except Exception as e:  # noqa: BLE001
            result["probe_error_micro"] = f"{type(e).__name__}: {e}"
            flops += n_micro * cf
            byts += n_micro * cb
            ici += n_micro * ci_
            dci += n_micro * cd
    else:
        flops += cf
        byts += cb
        ici += ci_
        dci += cd
    result["probes"] = probes
    result["loop_specs"] = loop_specs
    result["n_micro"] = n_micro
    result["flops_per_dev"] = flops
    result["bytes_per_dev"] = byts
    result["coll_ici_per_dev"] = ici
    result["coll_dci_per_dev"] = dci

    # --- roofline ---------------------------------------------------------------
    mf = cfg.model_flops(shape)
    terms = roofline(flops * n_dev, byts * n_dev, ici, n_dev,
                     coll_bytes_dci_per_chip=dci, model_flops=mf)
    result["roofline"] = {
        "compute_s": terms.compute_s, "memory_s": terms.memory_s,
        "collective_s": terms.collective_s, "dominant": terms.dominant,
        "step_time_s": terms.step_time_s,
        "model_flops": mf,
        "model_flops_s": terms.model_flops_s,
        "useful_flops_ratio": mf / max(flops * n_dev, 1.0),
        "roofline_fraction": terms.roofline_fraction,
    }
    result["ok"] = result["mem"]["peak_gib"] <= TPU_V5E.hbm_bytes / 2**30
    result["fits_hbm"] = result["ok"]
    result["ok"] = True   # compile success is the dry-run gate; HBM noted
    result["wall_s"] = round(time.time() - t_start, 1)
    if verbose:
        r = result["roofline"]
        print(f"[{arch} x {shape} x {result['mesh']}{tag}] ok "
              f"compile={result['compile_s']}s peak={result['mem']['peak_gib']:.2f}GiB "
              f"terms(c/m/n)={r['compute_s']:.4f}/{r['memory_s']:.4f}/"
              f"{r['collective_s']:.4f}s dom={r['dominant']} "
              f"frac={r['roofline_fraction']:.3f}", flush=True)
    return result


def cell_path(arch, shape, multi_pod, tag=""):
    m = "mp" if multi_pod else "sp"
    t = f"__{tag}" if tag else ""
    return RESULTS_DIR / f"{arch}__{shape}__{m}{t}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--seq-parallel", dest="sp", default=None,
                    choices=["on", "off"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    metavar="key=value",
                    help="Config override, e.g. --set n_microbatches=8")
    args = ap.parse_args()
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)

    overrides = {}
    if args.sp == "off":
        overrides["sequence_parallel"] = False
    if args.no_fsdp:
        overrides["fsdp_params"] = False
    cfg_overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                pass
        cfg_overrides[k] = v

    if args.all:
        cells = [(a, s, mp) for a in ASSIGNED for s in SHAPES
                 for mp in ((False, True) if args.multi_pod in (False,)
                            else (True,))]
        # single-pod first (roofline table), then multi-pod
        cells.sort(key=lambda c: (c[2], c[0], c[1]))
        for a, s, mp in cells:
            p = cell_path(a, s, mp, args.tag)
            if p.exists() and not args.force:
                continue
            res = run_cell(a, s, mp, plan_overrides=overrides, tag=args.tag,
                           cfg_overrides=cfg_overrides)
            p.write_text(json.dumps(res, indent=1, default=str))
            gc.collect()
        return

    res = run_cell(args.arch, args.shape, args.multi_pod,
                   plan_overrides=overrides, tag=args.tag,
                   cfg_overrides=cfg_overrides)
    p = cell_path(args.arch, args.shape, args.multi_pod, args.tag)
    p.write_text(json.dumps(res, indent=1, default=str))
    if not res.get("ok", False) and not res.get("skipped"):
        print(res.get("error"))
        print(res.get("traceback", "")[-2000:])
        raise SystemExit(1)


if __name__ == "__main__":
    main()
