import os
# placeholder-device tool: 512 CPU devices, and never the accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh).

Proves the distribution config is coherent without real hardware: 512
placeholder CPU devices stand in for 2 TPU v5e pods.  For each pair we
record ``compiled.memory_analysis()`` (fits-per-device proof),
``compiled.cost_analysis()`` (FLOPs/bytes) and the collective traffic
parsed from the post-SPMD HLO — the three §Roofline terms derive from
these (benchmarks/roofline.py).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""
import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.config import SHAPES                     # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import all_pairs, build_lowering  # noqa: E402

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "s8": 1, "s16": 2, "s32": 4, "s64": 8,
    "u4": 0.5, "u8": 1, "u16": 2, "u32": 4, "u64": 8,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _result_bytes(line: str) -> float:
    """Bytes of the op's result (post-SPMD per-device shape).  Tuples
    (e.g. fused all-reduces) sum their elements."""
    lhs = line.split(" = ", 1)[1] if " = " in line else line
    # only look at the result type: everything before the op name call
    head = lhs.split("(", 1)[0]
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(head):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _IOTA_GROUPS_RE.search(line)   # iota replica group list [n,m]
    if m:
        return int(m.group(2))
    return 2


def collective_stats(hlo_text: str) -> dict:
    """Per-device collective traffic estimate from optimized HLO.

    Ring-model traffic per device given the per-device result bytes R
    and group size n:  all-gather (n−1)/n·R, all-reduce 2(n−1)/n·R,
    reduce-scatter (n−1)·R, all-to-all (n−1)/n·R, permute R.
    """
    kinds = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
             "all-to-all": 0.0, "collective-permute": 0.0}
    counts = {k: 0 for k in kinds}
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith("%") or " = " in s:
            for kind in kinds:
                # match op invocation, not metadata mentions
                if re.search(rf"\)?\s{kind}(-start)?\(", s) or\
                        re.search(rf"= \S+ {kind}(-start)?\(", s):
                    r = _result_bytes(s)
                    n = _group_size(s)
                    if kind == "all-gather":
                        t = r * (n - 1) / n
                    elif kind == "all-reduce":
                        t = 2 * r * (n - 1) / n
                    elif kind == "reduce-scatter":
                        t = r * (n - 1)
                    elif kind == "all-to-all":
                        t = r * (n - 1) / n
                    else:
                        t = r
                    kinds[kind] += t
                    counts[kind] += 1
                    break
    total = sum(kinds.values())
    return {"bytes_per_device": total, "by_kind": kinds, "counts": counts}


def print_whales(hlo_text: str, top: int = 12) -> None:
    """Largest per-device tensor shapes in the optimized HLO (debug aid
    for memory hillclimbs — identifies what dominates temp bytes)."""
    sizes = {}
    for m in _SHAPE_RE.finditer(hlo_text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * _DTYPE_BYTES[dt]
        if b > 2 ** 27:
            key = f"{dt}[{dims}]"
            cnt = sizes.get(key, (0, 0))[1]
            sizes[key] = (b, cnt + 1)
    for k, (b, cnt) in sorted(sizes.items(), key=lambda kv: -kv[1][0])[:top]:
        print(f"   whale {b / 2**30:8.2f} GiB x{cnt:4d}  {k}")


def run_one(arch: str, shape: str, multi_pod: bool, out_dir: str,
            save_hlo: bool = False, whales: bool = False,
            variant: str = "baseline") -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    if variant == "w8kv8":
        from repro.launch.specs import build_quantized_decode
        low = build_quantized_decode(arch, shape, mesh)
        mesh_name += "+w8kv8"
    else:
        low = build_lowering(arch, shape, mesh)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "kind": low.kind, "n_devices": mesh.size}
    if low.skip:
        rec["skipped"] = low.skip
        print(f"[dryrun] {arch} × {shape} × {mesh_name}: SKIP ({low.skip})")
        return rec

    t0 = time.perf_counter()
    from jax.sharding import NamedSharding, PartitionSpec
    in_shard = jax.tree.map(
        lambda s: NamedSharding(mesh, s), low.in_specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    with jax.set_mesh(mesh):
        jitted = jax.jit(low.step_fn, in_shardings=in_shard,
                         donate_argnums=low.donate)
        lowered = jitted.lower(*low.args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    # trip-count-aware re-derivation (cost_analysis counts a while body
    # once regardless of its trip count — see launch/hlo_analysis.py)
    from repro.launch.hlo_analysis import analyze
    hlo_costs = analyze(hlo)

    rec.update({
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", 0.0)),
        "hlo_flops_per_device": hlo_costs.flops,
        "hlo_bytes_per_device": hlo_costs.bytes,
        "hlo_collective_bytes_per_device": hlo_costs.coll_bytes,
        "hlo_collective_by_kind": hlo_costs.coll_by_kind,
        "hlo_collective_counts": hlo_costs.coll_counts,
        "collective": coll,
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "code_bytes": int(getattr(
                mem, "generated_code_size_in_bytes", 0)),
        },
    })
    arg_b = rec["memory"]["argument_bytes"]
    tmp_b = rec["memory"]["temp_bytes"]
    print(f"[dryrun] {arch} × {shape} × {mesh_name}: OK  "
          f"compile={t_compile:.1f}s  args={arg_b / 2**30:.2f}GiB  "
          f"temp={tmp_b / 2**30:.2f}GiB  "
          f"flops/dev={hlo_costs.flops:.3e}  "
          f"bytes/dev={hlo_costs.bytes:.3e}  "
          f"coll={hlo_costs.coll_bytes / 2**30:.3f}GiB")
    print(f"         memory_analysis: {mem}")
    if whales:
        print_whales(hlo)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{arch}__{shape}__{mesh_name}.json"
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(rec, f, indent=1)
        if save_hlo:
            with open(os.path.join(out_dir, name[:-5] + ".hlo.txt"),
                      "w") as f:
                f.write(hlo)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--whales", action="store_true",
                    help="print the largest per-device HLO tensors")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "w8kv8"])
    args = ap.parse_args()

    pairs = list(all_pairs()) if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in pairs:
        for mp in meshes:
            try:
                run_one(arch, shape, mp, args.out, args.save_hlo,
                        args.whales, args.variant)
            except Exception as e:   # noqa: BLE001
                failures.append((arch, shape, mp, repr(e)))
                print(f"[dryrun] {arch} × {shape} × "
                      f"{'2x16x16' if mp else '16x16'}: FAIL {e!r}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        return 1
    print("\nall dry-runs passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
