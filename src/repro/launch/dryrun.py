import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU dry run: never claim a chip

# NOTE: no `from __future__ import annotations` here — the XLA_FLAGS and
# JAX_PLATFORMS exports above must stay the first executable statements,
# before any jax import.

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

For each cell this produces
  * a FULL compile (scan-over-layers) on the requested mesh — proves the
    sharding config is coherent, yields memory_analysis();
  * two PROBE compiles (reduced layer count, scans fully unrolled) on the
    single-pod mesh — XLA HloCostAnalysis counts while bodies once, so true
    FLOPs/bytes/collective-bytes are recovered by linear extrapolation:
        f(L) = a + b*L  measured at L = p and L = 2p.
  * the three roofline terms (hardware constants: TPU v5e) plus the
    Distributed Data Calculator's *predicted* terms for comparison.

Results are cached as JSON under experiments/dryrun/ (one file per cell) so
the sweep is resumable.  Run:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
        --shape train_4k --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --all   (subprocess sweep)
"""

import argparse
import dataclasses
import json
import re
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import (ArchConfig, RunConfig, SHAPES, ShapeConfig,
                                shape_applies)
from repro.core import distcalc
from repro.core.hardware import TPU_V5E
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import build
from repro.models.registry import Model
from repro.parallel import (batch_sharding, cache_shardings, data_axes,
                            param_shardings, state_shardings)
from repro.parallel import ctx
from repro.parallel.sharding import embeds_sharding
from repro.train.loop import TrainState, init_state, make_train_step
from repro.train.serve import make_prefill_step, make_serve_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

#: probe layer counts per family pattern period
PROBE_PERIOD = {"dense": 2, "moe": 2, "vlm": 2, "audio": 2,
                "hybrid": 6, "ssm": 4}


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------
def input_specs(cfg: ArchConfig, shape: ShapeConfig
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "decode":
        return {"token": jax.ShapeDtypeStruct((b,), i32),
                "pos": jax.ShapeDtypeStruct((b,), i32)}
    if cfg.family == "audio":
        # half source frames, half target tokens (total = seq_len)
        return {"tokens": jax.ShapeDtypeStruct((b, s // 2), i32),
                "labels": jax.ShapeDtypeStruct((b, s // 2), i32),
                "embeds": jax.ShapeDtypeStruct((b, s // 2, cfg.d_model),
                                               jnp.float32)}
    if cfg.family == "vlm":
        txt = s - cfg.n_patches
        return {"tokens": jax.ShapeDtypeStruct((b, txt), i32),
                "labels": jax.ShapeDtypeStruct((b, txt), i32),
                "embeds": jax.ShapeDtypeStruct((b, cfg.n_patches,
                                                cfg.d_model), jnp.float32)}
    return {"tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32)}


def _batch_shardings(specs: Dict, mesh: Mesh, batch: int) -> Dict:
    out = {}
    for key, sds in specs.items():
        if key == "embeds":
            out[key] = embeds_sharding(mesh, batch)
        else:
            out[key] = batch_sharding(mesh, batch, ndim=len(sds.shape))
    return out


def _logits_sharding(mesh: Mesh, cfg: ArchConfig, batch: int
                     ) -> NamedSharding:
    axes = data_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    first = (axes if len(axes) > 1 else axes[0]) \
        if axes and batch % total == 0 else None
    vocab_axis = "model" if "model" in mesh.axis_names and \
        cfg.vocab_size % mesh.shape["model"] == 0 else None
    return NamedSharding(mesh, P(first, vocab_axis))


# ---------------------------------------------------------------------------
# Lower + compile one cell
# ---------------------------------------------------------------------------
#: per-chip activation-stash budget driving the microbatch policy (bytes)
STASH_BUDGET = 2 << 30


def pick_microbatch(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                    seq_parallel: bool) -> int:
    """Gradient-accumulation policy: smallest number of microbatches such
    that the per-chip remat stash (one [b_micro, S, D] residual per layer)
    fits the budget.  Microbatch size must stay divisible by the dp ways."""
    dp = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                      if a in mesh.axis_names]))
    sp = mesh.shape.get("model", 1) if seq_parallel and \
        shape.seq_len % mesh.shape.get("model", 1) == 0 else 1
    cb = 2 if cfg.compute_dtype == "bfloat16" else 4
    layers = cfg.n_layers + cfg.n_encoder_layers
    micro = shape.global_batch
    while micro > dp:
        stash = micro * shape.seq_len * cfg.d_model * cb * layers / (dp * sp)
        if stash <= STASH_BUDGET:
            break
        micro //= 2
    return max(micro, min(dp, shape.global_batch))


def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
               seq_parallel: Optional[bool] = None,
               microbatch: Optional[int] = None,
               fsdp: bool = True,
               ep: bool = True,
               moment_dtype: str = "float32",
               grad_compression: bool = False) -> Tuple[Any, Any]:
    """Returns (lowered, compiled) for the cell's step function."""
    model = build(cfg)
    specs = input_specs(cfg, shape)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)  # PRNGKey placeholder
    sp = shape.kind == "train" if seq_parallel is None else seq_parallel
    mdt = jnp.dtype(moment_dtype)

    if shape.kind == "train":
        micro = pick_microbatch(cfg, shape, mesh, sp) \
            if microbatch is None else microbatch
        run = RunConfig(microbatch=micro, grad_compression=grad_compression)
        abstract_state = jax.eval_shape(
            lambda k: init_state(model, k, mdt), jax.random.PRNGKey(0))
        state_sh = state_shardings(abstract_state, mesh, fsdp=fsdp,
                                   ep=ep)
        batch_sh = _batch_shardings(specs, mesh, shape.global_batch)
        step = make_train_step(model, run)
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh,
                                        NamedSharding(mesh, P())),
                         donate_argnums=(0,))
        with mesh, ctx.mesh_context(mesh), \
                ctx.options(seq_parallel=sp, expert_parallel=ep):
            lowered = jitted.lower(abstract_state, specs)
            compiled = lowered.compile()
        return lowered, compiled

    if shape.kind == "prefill":
        abstract_params = jax.eval_shape(
            lambda k: model.init(k), jax.random.PRNGKey(0))
        p_sh = param_shardings(abstract_params, mesh, fsdp=fsdp, ep=ep)
        batch_sh = _batch_shardings(specs, mesh, shape.global_batch)
        step = make_prefill_step(model, max_len=shape.seq_len)
        kwargs = {}
        args: Tuple = (abstract_params, specs.get("tokens"))
        in_sh: Tuple = (p_sh, batch_sh.get("tokens"))
        if "embeds" in specs:
            args = args + (specs["embeds"],)
            in_sh = in_sh + (batch_sh["embeds"],)
        jitted = jax.jit(step, in_shardings=in_sh)
        with mesh, ctx.mesh_context(mesh), \
                ctx.options(seq_parallel=sp, expert_parallel=ep):
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
        return lowered, compiled

    # decode
    model = build(cfg)
    abstract_params = jax.eval_shape(
        lambda k: model.init(k), jax.random.PRNGKey(0))
    p_sh = param_shardings(abstract_params, mesh, fsdp=fsdp, ep=ep)
    kw = {"src_len": 4096} if cfg.family == "audio" else {}
    abstract_cache = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len, **kw))
    c_sh = cache_shardings(abstract_cache, mesh, shape.global_batch, cfg)
    tok_sh = batch_sharding(mesh, shape.global_batch, ndim=1)
    step = make_serve_step(model)
    jitted = jax.jit(
        step, in_shardings=(p_sh, c_sh, tok_sh, tok_sh),
        out_shardings=(_logits_sharding(mesh, cfg, shape.global_batch),
                       c_sh),
        donate_argnums=(1,))
    with mesh, ctx.mesh_context(mesh), \
            ctx.options(seq_parallel=False, expert_parallel=ep):
        lowered = jitted.lower(abstract_params, abstract_cache,
                               input_specs(cfg, shape)["token"],
                               input_specs(cfg, shape)["pos"])
        compiled = lowered.compile()
    return lowered, compiled


# ---------------------------------------------------------------------------
# Cost extraction
# ---------------------------------------------------------------------------
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> float:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        elems = 1.0
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        total += elems * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes per collective kind from optimized HLO.

    Per-chip data-movement factors (ring algorithms): all-reduce = 2x
    result; reduce-scatter = result x group (input is the full buffer);
    all-gather / all-to-all / permute = 1x result.
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+"
                     r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start)?\(", stripped)
        if not m:
            continue
        kind = m.group(2)
        if m.group(3) and f"{kind}-done" in hlo_text:
            pass  # started op; result shape still correct
        result_bytes = _shape_bytes(m.group(1))
        out[kind] += result_bytes
        counts[kind] += 1
    moved = (2.0 * out["all-reduce"] + out["all-gather"] +
             out["reduce-scatter"] + out["all-to-all"] +
             out["collective-permute"])
    return {"per_kind_result_bytes": out, "counts": counts,
            "moved_bytes_per_chip": moved}


def extract_costs(lowered, compiled) -> Dict[str, Any]:
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    mem_fields = {}
    if mem is not None:
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
            mem_fields[field] = getattr(mem, field, None)
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "memory": mem_fields, "collectives": coll}


def probe_config(cfg: ArchConfig, n_layers: int) -> ArchConfig:
    changes: Dict[str, Any] = {"n_layers": n_layers, "scan_unroll": True}
    if cfg.is_encdec:
        changes["n_encoder_layers"] = n_layers
    return dataclasses.replace(cfg, **changes)


def measure_cell(arch: str, shape_name: str, mesh_kind: str,
                 with_probes: bool = True,
                 variant: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """``variant`` overrides (seq_parallel / microbatch / fsdp /
    moment_dtype) — the §Perf hillclimb's A/B knobs; None = defaults."""
    variant = variant or {}
    cfg = get_config(arch)
    if "attn_impl" in variant:
        cfg = dataclasses.replace(cfg, attn_impl=variant["attn_impl"])
    shape = SHAPES[shape_name]
    applies, reason = shape_applies(cfg, shape)
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": variant, "time": time.time()}
    if not applies:
        record["skipped"] = reason
        return record

    if "mesh_shape" in variant:  # e.g. (32, 8): same 256 chips, TP=8
        d, m = variant["mesh_shape"]
        mesh = make_mesh((d, m), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    sp = variant.get("seq_parallel", shape.kind == "train")
    if shape.kind == "train":
        record["microbatch"] = variant.get(
            "microbatch", pick_microbatch(cfg, shape, mesh, sp))
        record["n_microbatches"] = shape.global_batch // record["microbatch"]
    record["seq_parallel"] = sp
    kw = dict(seq_parallel=sp,
              microbatch=record.get("microbatch"),
              fsdp=variant.get("fsdp", True),
              ep=variant.get("ep", True),
              moment_dtype=variant.get("moment_dtype", "float32"),
              grad_compression=variant.get("grad_compression", False))
    t0 = time.perf_counter()
    lowered, compiled = lower_cell(cfg, shape, mesh, **kw)
    record["compile_seconds"] = time.perf_counter() - t0
    record["full"] = extract_costs(lowered, compiled)
    del lowered, compiled

    if with_probes and mesh_kind == "single":
        p = PROBE_PERIOD[cfg.family]
        probes = {}
        for mult in (1, 2):
            pc = probe_config(cfg, p * mult)
            # probes run without gradient accumulation: the microbatch scan
            # is a while loop HloCostAnalysis counts once; a single pass has
            # identical FLOPs (the accumulated variant re-gathers FSDP
            # params n_micro times — added analytically in §Roofline)
            pkw = dict(kw, microbatch=shape.global_batch)
            lo, co = lower_cell(pc, shape, mesh, **pkw)
            probes[mult] = extract_costs(lo, co)
            del lo, co
        record["probes"] = {"period": p, "p1": probes[1], "p2": probes[2]}
        record["extrapolated"] = extrapolate(cfg, probes[1], probes[2], p)

    record["distcalc"] = predicted_terms(cfg, shape, mesh_kind)
    record["roofline"] = roofline_terms(cfg, shape, mesh_kind, record)
    return record


def extrapolate(cfg: ArchConfig, p1: Dict, p2: Dict, period: int
                ) -> Dict[str, float]:
    """f(L) = a + b*L measured at L=period and 2*period."""
    L = cfg.n_layers
    out = {}
    for key, get in (("flops", lambda r: r["flops"]),
                     ("bytes_accessed", lambda r: r["bytes_accessed"]),
                     ("collective_bytes",
                      lambda r: r["collectives"]["moved_bytes_per_chip"])):
        f1, f2 = get(p1), get(p2)
        b = (f2 - f1) / period
        a = f1 - b * period
        out[key] = max(a + b * L, 0.0)
    return out


def predicted_terms(cfg: ArchConfig, shape: ShapeConfig | str,
                    mesh_kind: str) -> Dict[str, Any]:
    if isinstance(shape, str):
        shape = SHAPES[shape]
    mesh_spec = distcalc.MeshSpec(pods=2 if mesh_kind == "multi" else 1)
    strat, terms = distcalc.complete_strategy(cfg, shape, mesh_spec)
    return {"strategy": strat.describe(), **terms.to_json()}


def roofline_terms(cfg: ArchConfig, shape: ShapeConfig, mesh_kind: str,
                   record: Dict) -> Dict[str, Any]:
    """Three-term roofline from the measured (extrapolated) HLO costs.

    XLA reports whole-program flops for the SPMD program = per-chip flops.
    """
    chips = 512 if mesh_kind == "multi" else 256
    src = record.get("extrapolated") or {
        "flops": record["full"]["flops"],
        "bytes_accessed": record["full"]["bytes_accessed"],
        "collective_bytes":
            record["full"]["collectives"]["moved_bytes_per_chip"]}
    compute_s = src["flops"] / TPU_V5E.peak_flops_bf16
    memory_s = src["bytes_accessed"] / TPU_V5E.hbm_bw
    collective_s = src["collective_bytes"] / TPU_V5E.ici_bw
    mf = distcalc.model_flops(cfg, shape)
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s,
             "dominant": max([("compute", compute_s), ("memory", memory_s),
                              ("collective", collective_s)],
                             key=lambda kv: kv[1])[0],
             "model_flops_total": mf,
             "model_flops_per_chip": mf / chips,
             "useful_flops_ratio":
                 (mf / chips) / src["flops"] if src["flops"] else 0.0,
             "roofline_fraction":
                 compute_s / max(compute_s, memory_s, collective_s)
                 if max(compute_s, memory_s, collective_s) > 0 else 0.0}
    return terms


# ---------------------------------------------------------------------------
# Sweep driver (subprocess per cell: isolates compiles, caches results)
# ---------------------------------------------------------------------------
def cell_path(arch: str, shape: str, mesh: str, tag: str = "") -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    return os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh}{suffix}.json")


def run_one(arch: str, shape: str, mesh: str, probes: bool,
            variant: Optional[Dict[str, Any]] = None,
            tag: str = "") -> Dict:
    record = measure_cell(arch, shape, mesh, with_probes=probes,
                          variant=variant)
    with open(cell_path(arch, shape, mesh, tag), "w") as fh:
        json.dump(record, fh, indent=1)
    return record


def sweep(mesh_kinds=("single", "multi"), force: bool = False) -> None:
    cells = []
    for arch in ARCH_IDS:
        for shape in SHAPES:
            for mesh in mesh_kinds:
                cells.append((arch, shape, mesh))
    for arch, shape, mesh in cells:
        path = cell_path(arch, shape, mesh)
        if os.path.exists(path) and not force:
            print(f"skip (cached) {arch} {shape} {mesh}")
            continue
        print(f"=== {arch} {shape} {mesh} ===", flush=True)
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
             "--shape", shape, "--mesh", mesh],
            env=dict(os.environ),
            capture_output=True, text=True, timeout=7200)
        if proc.returncode != 0:
            print(f"FAILED {arch} {shape} {mesh}:\n{proc.stdout[-2000:]}"
                  f"\n{proc.stderr[-4000:]}", flush=True)
            with open(path, "w") as fh:
                json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                           "error": proc.stderr[-4000:]}, fh)
        else:
            print(proc.stdout[-800:], flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    # §Perf hillclimb knobs (written to a --tag'd variant file)
    ap.add_argument("--tag", default="", help="variant file suffix")
    ap.add_argument("--no-sp", action="store_true",
                    help="disable sequence parallelism")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="replicate params across data (DP baseline)")
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--moment-dtype", default=None,
                    choices=(None, "float32", "bfloat16"))
    ap.add_argument("--grad-compress", action="store_true",
                    help="bf16 gradient reduction")
    ap.add_argument("--no-ep", action="store_true",
                    help="replicate experts; TP inside the expert ffn")
    ap.add_argument("--attn-impl", default=None, choices=("xla", "skip"),
                    help="'skip' = attention-internal-bytes ablation probe")
    ap.add_argument("--mesh-shape", default=None,
                    help="single-pod mesh reshape, e.g. 32x8")
    args = ap.parse_args()
    if args.all:
        sweep(force=args.force)
        return
    variant: Dict[str, Any] = {}
    if args.no_sp:
        variant["seq_parallel"] = False
    if args.no_fsdp:
        variant["fsdp"] = False
    if args.microbatch is not None:
        variant["microbatch"] = args.microbatch
    if args.moment_dtype:
        variant["moment_dtype"] = args.moment_dtype
    if args.grad_compress:
        variant["grad_compression"] = True
    if args.no_ep:
        variant["ep"] = False
    if args.attn_impl:
        variant["attn_impl"] = args.attn_impl
    if args.mesh_shape:
        variant["mesh_shape"] = tuple(
            int(x) for x in args.mesh_shape.split("x"))
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    for mesh in meshes:
        record = run_one(args.arch, args.shape, mesh,
                         probes=not args.no_probes,
                         variant=variant or None, tag=args.tag)
        summary = {k: record.get(k) for k in
                   ("arch", "shape", "mesh", "skipped", "compile_seconds",
                    "variant", "microbatch")}
        if "roofline" in record:
            summary["roofline"] = record["roofline"]
        if "full" in record:
            summary["memory"] = record["full"]["memory"]
            summary["collectives"] = record["full"]["collectives"][
                "per_kind_result_bytes"]
        print(json.dumps(summary, indent=1, default=str))


if __name__ == "__main__":
    main()
