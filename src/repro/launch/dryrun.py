import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (architecture x input shape)
on the production mesh, and extract the roofline terms.

The two lines above MUST run before any other import (jax locks the device
count at first backend init); everything below assumes 512 host devices.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes

Per combo it writes artifacts/dryrun/<arch>__<shape>__<mesh>.json with:
  flops / bytes from compiled.cost_analysis()  (per-device, post-SPMD),
  per-category collective output bytes parsed from the optimized HLO,
  memory_analysis (argument/output/temp/generated code bytes per device),
  and wall-clock lower/compile times.
"""

import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import (
    batch_shardings,
    cache_shardings,
    opt_shardings,
    param_shardings,
    token_sharding,
)
from repro.models.model import INPUT_SHAPES, decode_step, input_specs, prefill, train_step
from repro.models.params import param_shapes
from repro.optim.optimizers import adamw_init
from repro.sharding.rules import ShardingPolicy, mesh_context

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(stext: str) -> int:
    """Sum byte sizes of every 'dtype[dims]' in a (possibly tuple) type."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(stext):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-category totals of collective OUTPUT bytes (per device, since the
    module is post-SPMD) + op counts.  `*-start` async forms are counted via
    their start op; `*-done` is skipped to avoid double counting."""
    out = {c: {"bytes": 0, "count": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        lhs, rhs = s.split("=", 1)
        rhs = rhs.strip()
        m = re.match(r"^(\([^)]*\)|\w+\[[\d,]*\](?:\{[^}]*\})?)\s+([\w-]+)", rhs)
        if not m:
            continue
        opcode = m.group(2)
        base = opcode.removesuffix("-start")
        if opcode.endswith("-done") or base not in _COLLECTIVES:
            continue
        out[base]["bytes"] += _shape_bytes(m.group(1))
        out[base]["count"] += 1
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


def _mem_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # backend without memory analysis
        return {"error": str(e)}
    if ma is None:
        return {"error": "memory_analysis() returned None"}
    keys = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
        "host_argument_size_in_bytes",
        "host_output_size_in_bytes",
        "host_temp_size_in_bytes",
    )
    return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}


def _cost_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items() if isinstance(v, (int, float))}


def build_lowerable(cfg, mesh, shape_name: str, policy: ShardingPolicy):
    """Returns (jitted_fn, abstract_args)."""
    kind = INPUT_SHAPES[shape_name]["kind"]
    p_sh = param_shardings(cfg, mesh, policy)
    p_shapes = param_shapes(cfg)
    donate = (0, 1) if policy.donate else ()

    if kind == "train":
        o_sh = opt_shardings(cfg, mesh, policy)
        b_sh = batch_shardings(cfg, mesh, shape_name)
        o_shapes = jax.eval_shape(adamw_init, p_shapes)
        fn = lambda p, o, b: train_step(p, o, cfg, b, policy, lr=1e-4)
        jfn = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh), out_shardings=(p_sh, o_sh, None),
                      donate_argnums=donate)
        return jfn, (p_shapes, o_shapes, input_specs(cfg, shape_name))

    if kind == "prefill":
        b_sh = batch_shardings(cfg, mesh, shape_name)
        c_sh = cache_shardings(cfg, mesh, "decode_32k")
        fn = lambda p, b: prefill(p, cfg, b, policy)
        jfn = jax.jit(fn, in_shardings=(p_sh, b_sh), out_shardings=(None, c_sh))
        return jfn, (p_shapes, input_specs(cfg, shape_name))

    # decode: the cache buffer is donated (in-place steady-state serving)
    specs = input_specs(cfg, shape_name)
    c_sh = cache_shardings(cfg, mesh, shape_name)
    t_sh = token_sharding(cfg, mesh, shape_name)
    fn = lambda p, c, t: decode_step(p, cfg, c, t, policy)
    jfn = jax.jit(fn, in_shardings=(p_sh, c_sh, t_sh), out_shardings=(None, c_sh),
                  donate_argnums=(1,) if policy.donate else ())
    return jfn, (p_shapes, specs["cache"], specs["token"])


def applicable(cfg, shape_name: str) -> tuple[bool, str]:
    kind = INPUT_SHAPES[shape_name]["kind"]
    if kind == "decode" and not cfg.has_decoder:
        return False, "encoder-only arch has no decode step"
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return False, "full-attention arch; long_500k needs sub-quadratic decode (DESIGN.md)"
    return True, ""


def depth_variant(cfg, k: int):
    """Same-family config with k scanned blocks (for cost extrapolation)."""
    import dataclasses

    if cfg.arch_type == "hybrid":
        return dataclasses.replace(cfg, n_layers=k * cfg.attn_every)
    if cfg.arch_type == "encdec":
        return dataclasses.replace(cfg, n_layers=k, n_enc_layers=k)
    return dataclasses.replace(cfg, n_layers=k)


def _measure(cfg, mesh, shape_name, policy, want_hlo: bool):
    with mesh_context(mesh):
        t0 = time.time()
        jfn, args = build_lowerable(cfg, mesh, shape_name, policy)
        lowered = jfn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        out = {
            "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "memory": _mem_dict(compiled),
            "cost": _cost_dict(compiled),
        }
        if want_hlo:
            hlo = compiled.as_text()
            out["hlo_chars"] = len(hlo)
            out["collectives"] = parse_collectives(hlo)
            del hlo
        return out


_EXTRAP_KEYS = ("flops", "bytes accessed", "transcendentals", "optimal_seconds")


def _extrapolate(da: dict, db: dict, nb: int, ka: int = 1, kb: int = 2) -> dict:
    """Linear in block count: F(nb) = Fa + (nb-ka) * (Fb-Fa)/(kb-ka).

    Exact because every scanned block is shape-identical; the intercept
    carries the depth-independent embed/unembed/loss cost.
    """
    span = kb - ka
    out = {"cost": {}, "collectives": {}, "per_block": {}}
    for k in _EXTRAP_KEYS:
        if k in da["cost"] and k in db["cost"]:
            # per-block cost cannot be negative; depth-1 programs sometimes
            # get boundary-specialized shardings, so clamp at zero.
            per = max((db["cost"][k] - da["cost"][k]) / span, 0.0)
            out["cost"][k] = da["cost"][k] + (nb - ka) * per
            out["per_block"][k] = per
    ca, cb = da.get("collectives", {}), db.get("collectives", {})
    for cat in list(_COLLECTIVES) + ["total_bytes"]:
        va = ca.get(cat, {}).get("bytes", 0) if cat != "total_bytes" else ca.get(cat, 0)
        vb = cb.get(cat, {}).get("bytes", 0) if cat != "total_bytes" else cb.get(cat, 0)
        per = max((vb - va) / span, 0.0)
        out["collectives"][cat] = va + (nb - ka) * per
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool, policy: ShardingPolicy, out_dir: str) -> dict:
    cfg = get_config(arch, "full")
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_devices": 512 if multi_pod else 256,
        "params": None, "status": None,
        "policy": {
            "seq_parallel": policy.seq_parallel, "zero1": policy.zero1,
            "remat": policy.remat, "fsdp": policy.fsdp,
            "attn_chunk": policy.attn_chunk, "donate": policy.donate,
        },
    }
    ok, why = applicable(cfg, shape_name)
    if not ok:
        result["status"] = "skipped"
        result["reason"] = why
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.models.params import count_params

    result["params"] = count_params(cfg)
    result["active_params"] = cfg.active_param_count()
    try:
        # PASS A -- the lowering/fit proof: full depth, rolled scan (the
        # deployable program; while-loop body reuses buffers, so
        # memory_analysis is the realistic per-device footprint).
        rolled = dataclasses_replace_policy(policy, scan_unroll=False)
        a = _measure(cfg, mesh, shape_name, rolled, want_hlo=False)
        result.update(lower_s=a["lower_s"], compile_s=a["compile_s"], memory=a["memory"])
        result["cost_rolled"] = a["cost"]

        # PASS B -- cost accounting: XLA counts a while body ONCE, so flops/
        # bytes/collectives come from depth-2 and depth-4 UNROLLED compiles,
        # extrapolated linearly (exact; blocks are shape-identical).
        # Single-pod only: the roofline table is single-pod by spec.
        if not multi_pod:
            unrolled = dataclasses_replace_policy(policy, scan_unroll=True)
            d1 = _measure(depth_variant(cfg, 1), mesh, shape_name, unrolled, want_hlo=True)
            d2 = _measure(depth_variant(cfg, 2), mesh, shape_name, unrolled, want_hlo=True)
            result["cost_depth"] = {"d1": d1["cost"], "d2": d2["cost"]}
            result["collectives_depth"] = {"d1": d1["collectives"], "d2": d2["collectives"]}
            ex = _extrapolate(d1, d2, cfg.n_blocks)
            result["cost"] = ex["cost"]
            result["collectives"] = ex["collectives"]
            result["per_block"] = ex["per_block"]
        result["status"] = "ok"
        print({k: result["memory"].get(k) for k in ("temp_size_in_bytes", "argument_size_in_bytes")})
        if "cost" in result:
            print({k: result["cost"].get(k) for k in ("flops", "bytes accessed")},
                  "coll:", result.get("collectives", {}).get("total_bytes"))
    except Exception as e:
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    return result


def dataclasses_replace_policy(policy: ShardingPolicy, **kw) -> ShardingPolicy:
    import dataclasses

    return dataclasses.replace(policy, **kw)


def save_result(res: dict, out_dir: str) -> str:
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{res['arch']}__{res['shape']}__{res['mesh']}.json"
    path = os.path.join(out_dir, fname)
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    return path


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=[a.replace("_", "-") for a in ARCH_IDS] + list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out-dir", default=os.path.normpath(ARTIFACTS))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-unroll", action="store_true",
                    help="keep layer scans rolled (faster compile, body-once flop counts)")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--attn-chunk", type=int, default=2048)
    ap.add_argument("--tag", default="", help="suffix for ablation artifacts")
    args = ap.parse_args()

    policy = ShardingPolicy(
        seq_parallel=not args.no_seq_parallel,
        zero1=not args.no_zero1,
        remat=not args.no_remat,
        scan_unroll=not args.no_unroll,
        fsdp=not args.no_fsdp,
        donate=not args.no_donate,
        attn_chunk=args.attn_chunk,
    )

    combos = []
    archs = ARCH_IDS if args.all else [args.arch.replace("-", "_")]
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    n_fail = 0
    for a, s, mp in combos:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        tag = f"__{args.tag}" if args.tag else ""
        fname = os.path.join(args.out_dir, f"{a}__{s}__{mesh_name}{tag}.json")
        if args.skip_existing and os.path.exists(fname):
            print(f"[skip existing] {a} {s} {mesh_name}")
            continue
        print(f"=== {a} | {s} | {mesh_name} ===", flush=True)
        res = run_one(a, s, mp, policy, args.out_dir)
        if args.tag:
            res["tag"] = args.tag
            res_path = fname
            os.makedirs(args.out_dir, exist_ok=True)
            with open(res_path, "w") as f:
                json.dump(res, f, indent=1)
        else:
            res_path = save_result(res, args.out_dir)
        print(f"[{res['status']}] -> {res_path}", flush=True)
        if res["status"] == "error":
            n_fail += 1
            print(res.get("error"), flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
