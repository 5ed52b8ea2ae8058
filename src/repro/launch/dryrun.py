"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) pair, lower + compile the production
step function on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh,
print memory/cost analyses, extract collective bytes from the optimized
HLO, and persist everything to experiments/dryrun/*.json for the roofline
report (benchmarks/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch mistral-7b --shape decode_32k
  python -m repro.launch.dryrun --all                  # full 40-pair matrix
  python -m repro.launch.dryrun --all --multi-pod
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape decode_32k --spec
"""
import os

if __name__ == "__main__":
    # 512-placeholder-device override, entry-point ONLY: it must precede
    # the jax import below (the device count locks on first init), and
    # IMPORTING this module (test collection, benchmarks borrowing
    # collective_bytes) must never mutate jax device state.  The append-
    # don't-clobber / respect-caller-count policy lives in hostdev.
    # The dry-run lowers for placeholder CPU devices: pin the platform so
    # that neither this process nor the per-case children it starts
    # (_drive_subprocesses) ever claims a TPU on a host that has one.
    os.environ["JAX_PLATFORMS"] = "cpu"
    from repro.launch.hostdev import ensure_host_devices
    ensure_host_devices(512)

import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import ALL_ARCHS, ASSIGNED_ARCHS  # noqa: E402
from repro.launch.input_specs import SHAPES, resolve_case  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op (per-device program)."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for c in _COLLECTIVES:
            # match ` = <type> op-name(` incl. async `-start` variants
            m = re.search(rf"=\s+(.*?)\s+{c}(?:-start)?\(", line)
            if m:
                out[c] += _type_bytes(m.group(1))
                counts[c] += 1
                break
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    out["counts"] = counts
    return out


def _mem_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # backend without memory analysis
        return {"error": str(e)}
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes"]
    d = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            d[k] = int(v)
    d["total_hbm_bytes"] = (d.get("argument_size_in_bytes", 0)
                            + d.get("output_size_in_bytes", 0)
                            + d.get("temp_size_in_bytes", 0)
                            - d.get("alias_size_in_bytes", 0))
    return d


def _compile_case(case, mesh):
    from repro.distributed import act_sharding
    jfn = jax.jit(case.fn, in_shardings=case.in_shardings,
                  out_shardings=case.out_shardings,
                  donate_argnums=case.donate)
    with act_sharding.activated(mesh), mesh:
        lowered = jfn.lower(*case.args)
        compiled = lowered.compile()
    return compiled


def _cost_dict(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    return {k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and (
                k in ("flops", "bytes accessed", "transcendentals")
                or k.startswith("bytes accessed"))}


def run_case(arch: str, shape: str, multi_pod: bool,
             spec_step: bool = False, roofline: bool = False) -> dict:
    from repro.configs import get_config
    from repro.models import runtime_flags
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "spec_step": spec_step, "n_devices": 512 if multi_pod else 256}
    mesh = make_production_mesh(multi_pod=multi_pod)
    case = resolve_case(arch, shape, mesh, spec_step=spec_step)
    if case.skip_reason:
        rec["status"] = "skip"
        rec["skip_reason"] = case.skip_reason
        return rec
    t0 = time.time()
    compiled = _compile_case(case, mesh)
    rec["compile_s"] = round(time.time() - t0, 1)
    rec["cost"] = _cost_dict(compiled)
    rec["memory"] = _mem_dict(compiled)
    rec["collectives"] = collective_bytes(compiled.as_text())
    rec["status"] = "ok"

    if roofline:
        # Calibration: compile 1-period and 2-period variants with all scans
        # unrolled (exact HloCostAnalysis), extrapolate linearly in depth.
        cfg = get_config(arch)
        P, pre = cfg.pattern_period, len(cfg.prefix_blocks)
        L1, L2 = pre + P, pre + 2 * P
        calib = {"pattern_period": P, "prefix_layers": pre,
                 "full_layers": cfg.num_layers}
        runtime_flags.set_unroll(True)
        try:
            for tag, L in (("L1", L1), ("L2", L2)):
                c = resolve_case(arch, shape, mesh, spec_step=spec_step,
                                 num_layers=L)
                t0 = time.time()
                comp = _compile_case(c, mesh)
                calib[tag] = {"layers": L, "cost": _cost_dict(comp),
                              "collectives": collective_bytes(comp.as_text()),
                              "compile_s": round(time.time() - t0, 1)}
        finally:
            runtime_flags.set_unroll(False)
        rec["calib"] = calib
    return rec


def _drive_subprocesses(cases, args, timeout_s: int = 2400) -> None:
    """Run each case in an isolated subprocess: one pathological compile
    must not take down the rest of the matrix.  Caches finished cases."""
    import subprocess
    import sys
    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch, shape in cases:
        tag = "spec" if args.spec else "base"
        mesh_tag = "multipod" if args.multi_pod else "pod"
        fname = os.path.join(args.out,
                             f"{arch}__{shape}__{mesh_tag}__{tag}.json")
        if os.path.exists(fname):
            rec = json.load(open(fname))
            st = rec.get("status")
            calib_ok = (not args.roofline) or ("calib" in rec) \
                or st != "ok"
            if st in ("ok", "skip") and calib_ok:
                print(f"[cache] {arch:22s} {shape:12s} ({st})", flush=True)
                n_ok += st == "ok"
                n_skip += st == "skip"
                continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--out", args.out]
        for flag, on in (("--multi-pod", args.multi_pod),
                         ("--spec", args.spec),
                         ("--roofline", args.roofline)):
            if on:
                cmd.append(flag)
        err = ""
        try:
            r = subprocess.run(cmd, timeout=timeout_s,
                               capture_output=True, text=True)
            if r.returncode:
                err = (r.stdout[-400:] + r.stderr[-400:])
        except subprocess.TimeoutExpired:
            err = f"calibration timeout after {timeout_s}s"
        # the subprocess writes the base record BEFORE calibration: keep a
        # good base record even if calibration timed out / crashed
        if os.path.exists(fname):
            st = json.load(open(fname)).get("status", "fail")
            if st == "ok" and err:
                err = f"(base ok; {err})"
        else:
            st = "fail"
            with open(fname, "w") as f:
                json.dump({"arch": arch, "shape": shape, "status": "fail",
                           "error": err or "no output"}, f, indent=1)
        n_ok += st == "ok"
        n_skip += st == "skip"
        n_fail += st == "fail"
        print(f"[{st:4s}] {arch:22s} {shape:12s} {err[:120]}", flush=True)
    print(f"done: {n_ok} ok, {n_skip} skip, {n_fail} fail")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="run the full assigned 10x4 matrix")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--spec", action="store_true",
                    help="lower the speculative (k,w+1) serve step instead "
                         "of the 1-token baseline")
    ap.add_argument("--roofline", action="store_true",
                    help="add unrolled 1/2-period calibration compiles for "
                         "exact per-layer cost extrapolation")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        # cheap decode shapes first (bank results), train last
        order = ["decode_32k", "long_500k", "prefill_32k", "train_4k"]
        cases = [(a, s) for s in order for a in ASSIGNED_ARCHS]
        _drive_subprocesses(cases, args)
        return
    assert args.arch and args.shape, "--arch/--shape or --all"
    cases = [(args.arch, args.shape)]

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch, shape in cases:
        tag = "spec" if args.spec else "base"
        mesh_tag = "multipod" if args.multi_pod else "pod"
        fname = os.path.join(args.out,
                             f"{arch}__{shape}__{mesh_tag}__{tag}.json")
        try:
            # write the base record BEFORE calibration so a slow/killed
            # calibration never loses the lower+compile proof
            rec = run_case(arch, shape, args.multi_pod, spec_step=args.spec,
                           roofline=False)
            with open(fname, "w") as f:
                json.dump(rec, f, indent=1)
            if args.roofline and rec["status"] == "ok":
                rec = run_case(arch, shape, args.multi_pod,
                               spec_step=args.spec, roofline=True)
        except Exception:
            rec = {"arch": arch, "shape": shape, "status": "fail",
                   "error": traceback.format_exc()[-2000:]}
        with open(fname, "w") as f:
            json.dump(rec, f, indent=1)
        st = rec["status"]
        n_ok += st == "ok"
        n_skip += st == "skip"
        n_fail += st == "fail"
        extra = ""
        if st == "ok":
            extra = (f"flops/dev={rec['cost'].get('flops', 0):.3g} "
                     f"hbm/dev={rec['memory'].get('total_hbm_bytes', 0)/2**30:.2f}GiB "
                     f"coll/dev={rec['collectives']['total']/2**20:.1f}MiB "
                     f"compile={rec['compile_s']}s")
        elif st == "skip":
            extra = rec["skip_reason"]
        else:
            extra = rec["error"].splitlines()[-1][:160]
        print(f"[{st:4s}] {arch:22s} {shape:12s} {extra}", flush=True)
    print(f"done: {n_ok} ok, {n_skip} skip, {n_fail} fail")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
