import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The lines above MUST run before any other import (jax locks the backend and
# the device count at first init). Placeholder devices exist ONLY for the
# dry-run, which lowers on the CPU backend: it never takes a chip, so it and
# its --all children can run beside a process that holds one.

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes and extract the roofline terms from the compiled artifact.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--jobs 4] [--mesh both]
  python -m repro.launch.dryrun --report            # summarize results dir

Per cell this records: compile ok, memory_analysis (bytes/device),
cost_analysis (HLO FLOPs / bytes), per-collective byte totals parsed from the
optimized HLO, and the analytic MODEL_FLOPS for the §Roofline usefulness
ratio. Failures (sharding mismatch, OOM-at-compile, unsupported collective)
are bugs in the system — they are recorded and must be fixed, not skipped.
"""
import argparse
import json
import re
import subprocess
import sys
import time
import traceback

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# v5e constants (see launch/mesh.py)
CHIP_PEAK_FLOPS = 197e12
CHIP_HBM_BW = 819e9
ICI_BW_PER_CHIP = 4 * 50e9 / 2  # 4 links, half duplex-credited per direction

_COLL_RE = re.compile(
    r"(\w+\[[\d,]*\](?:\{[^}]*\})?)\s*"
)
_SHAPE_RE = re.compile(r"(bf16|f32|f16|f64|s32|s8|u32|u8|pred|s64|c64)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4, "u32": 4,
    "s8": 1, "u8": 1, "pred": 1, "s64": 8, "c64": 8,
}

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
# ring/bidirectional cost multiplier on output bytes
_COLL_FACTOR = {
    "all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
    "all-to-all": 1.0, "collective-permute": 1.0,
}


def parse_collective_bytes(hlo_text: str):
    """Sum output-shape bytes of collective ops in the optimized (SPMD,
    per-device) HLO. Returns {op: bytes} plus 'total' weighted by ring cost
    factors."""
    per_op = {k: 0.0 for k in COLLECTIVE_OPS}
    counts = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.*)$", s)
        if not m:
            continue
        rhs = m.group(1)
        op = None
        for cand in COLLECTIVE_OPS:
            # match "all-gather(" or "all-gather-start(" etc.
            if re.search(rf"\b{cand}(-start)?\(", rhs):
                op = cand
                break
        if op is None:
            continue
        # output shapes = everything before the op token
        head = rhs.split(op)[0]
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(head):
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        per_op[op] += nbytes
        counts[op] += 1
    total = sum(per_op[k] * _COLL_FACTOR[k] for k in per_op)
    return per_op, counts, total


def _compile_and_measure(arch, shape, mesh, kind, n_layers=None, unroll=False,
                         variant=None):
    import jax

    kw = {}
    if variant:
        kw["variant"] = variant
    if n_layers is None and not unroll:
        built = arch.build(shape, mesh, **kw)
    else:
        built = arch.build(shape, mesh, n_layers=n_layers, unroll=unroll, **kw)
    donate = ()
    if kind == "train":
        donate = (0, 1)
    elif kind == "decode":
        donate = (1,)
    with jax.set_mesh(mesh):
        kw = {}
        if built.out_shardings is not None:
            kw["out_shardings"] = built.out_shardings
        jitted = jax.jit(
            built.fn, in_shardings=built.in_shardings,
            donate_argnums=donate, **kw,
        )
        lowered = jitted.lower(*built.args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    per_op, counts, coll_total = parse_collective_bytes(hlo)
    return dict(
        mem=mem,
        flops=float(cost.get("flops", 0.0)),
        bytes_acc=float(cost.get("bytes accessed", 0.0)),
        per_op=per_op, counts=counts, coll_total=coll_total,
        meta=built.meta,
    )


def run_cell(
    arch_name: str, shape: str, multi_pod: bool, variant: str = None,
) -> dict:
    from repro.configs import get_arch
    from repro.launch.mesh import make_production_mesh

    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    arch = get_arch(arch_name)
    cell = arch.cells[shape]
    rec = dict(
        arch=arch_name, shape=shape,
        mesh="2x16x16" if multi_pod else "16x16",
        n_chips=int(n_chips), kind=cell.kind, variant=variant or "base",
    )
    if cell.skip:
        rec.update(status="skipped", reason=cell.skip)
        return rec
    try:
        # full-depth compile: THE deliverable (must succeed at the real config)
        full = _compile_and_measure(
            arch, shape, mesh, cell.kind, variant=variant
        )
        flops, bytes_acc = full["flops"], full["bytes_acc"]
        per_op, counts, coll_total = (
            full["per_op"], full["counts"], full["coll_total"]
        )
        calib = None
        if arch.layer_calib is not None:
            # XLA cost_analysis counts a scan body once — compile two reduced
            # depths and extrapolate per-layer terms to the real depth.
            L1, L2, Lf = arch.layer_calib
            m1 = _compile_and_measure(
                arch, shape, mesh, cell.kind, n_layers=L1, unroll=True
            )
            m2 = _compile_and_measure(
                arch, shape, mesh, cell.kind, n_layers=L2, unroll=True
            )
            dL = L2 - L1

            def extrap(a, b):
                slope = (b - a) / dL
                return a + slope * (Lf - L1)

            flops = extrap(m1["flops"], m2["flops"])
            bytes_acc = extrap(m1["bytes_acc"], m2["bytes_acc"])
            coll_total = extrap(m1["coll_total"], m2["coll_total"])
            per_op = {
                k: extrap(m1["per_op"][k], m2["per_op"][k]) for k in per_op
            }
            calib = dict(
                L1=L1, L2=L2, Lf=Lf,
                flops_raw=full["flops"],
                flops_L1=m1["flops"], flops_L2=m2["flops"],
            )
        # analytic attention correction (chunk scans are trip-count-
        # undercounted by cost_analysis; see configs/base.py)
        corr_f = float(full["meta"].get("attn_corr_flops", 0.0)) / n_chips
        corr_b = float(full["meta"].get("attn_corr_bytes", 0.0)) / n_chips
        flops += corr_f
        bytes_acc += corr_b
        mem = full["mem"]
        model_flops = float(full["meta"].get("model_flops", 0.0))
        t_compute = flops / CHIP_PEAK_FLOPS
        t_memory = bytes_acc / CHIP_HBM_BW
        t_coll = coll_total / ICI_BW_PER_CHIP
        rec.update(
            status="ok",
            seconds=round(time.perf_counter() - t0, 1),
            memory=dict(
                argument_bytes=mem.argument_size_in_bytes,
                output_bytes=mem.output_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes,
                alias_bytes=mem.alias_size_in_bytes,
                code_bytes=mem.generated_code_size_in_bytes,
            ),
            hlo_flops=flops,
            hlo_bytes=bytes_acc,
            collective_bytes=coll_total,
            collectives=per_op,
            collective_counts=counts,
            calibration=calib,
            model_flops=model_flops,
            useful_flops_ratio=(model_flops / max(n_chips, 1)) / max(flops, 1.0),
            roofline=dict(
                t_compute=t_compute,
                t_memory=t_memory,
                t_collective=t_coll,
                dominant=max(
                    [("compute", t_compute), ("memory", t_memory),
                     ("collective", t_coll)],
                    key=lambda kv: kv[1],
                )[0],
            ),
            meta={k: v for k, v in full["meta"].items()
                  if isinstance(v, (int, float, str, list))},
        )
    except Exception as e:  # a failure here is a bug to fix
        rec.update(
            status="fail", error=f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-4000:],
            seconds=round(time.perf_counter() - t0, 1),
        )
    return rec


def _result_path(arch, shape, mesh_tag, out_dir):
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh_tag}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="build variant (gnn: base|unsharded|halo)")
    args = ap.parse_args()
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)

    if args.report:
        report(out_dir)
        return

    if args.all:
        orchestrate(args, out_dir)
        return

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    ok = True
    for m in meshes:
        rec = run_cell(
            args.arch, args.shape, multi_pod=(m == "multi"),
            variant=args.variant,
        )
        tag = "2x16x16" if m == "multi" else "16x16"
        if args.variant:
            tag = f"{tag}__{args.variant}"
        path = _result_path(args.arch, args.shape, tag, out_dir)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        extra = (
            f" dominant={rec['roofline']['dominant']}"
            f" flops={rec['hlo_flops']:.3g}"
            f" coll={rec['collective_bytes']:.3g}B"
            if status == "ok" else rec.get("reason", rec.get("error", ""))[:120]
        )
        print(f"[{status}] {args.arch} {args.shape} {tag} "
              f"({rec.get('seconds', 0)}s){extra}", flush=True)
        ok &= status in ("ok", "skipped")
    sys.exit(0 if ok else 1)


def orchestrate(args, out_dir):
    """Run every (arch × shape × mesh) as subprocesses, --jobs at a time."""
    from repro.configs import list_cells

    meshes = ["single", "multi"] if args.mesh in ("both",) else [args.mesh]
    work = []
    for arch, shape, cell in list_cells():
        for m in meshes:
            tag = "2x16x16" if m == "multi" else "16x16"
            path = _result_path(arch, shape, tag, out_dir)
            if not args.force and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("status") in ("ok", "skipped"):
                        continue
            work.append((arch, shape, m))
    print(f"dry-run: {len(work)} cells to compile, jobs={args.jobs}")
    procs = []
    fails = 0
    done = 0

    def launch(item):
        arch, shape, m = item
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shape, "--mesh", m, "--out", out_dir,
        ]
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(
            os.path.dirname(__file__), "..", ".."
        )
        return subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        ), item

    queue = list(work)
    while queue or procs:
        while queue and len(procs) < args.jobs:
            procs.append(launch(queue.pop(0)))
        for p, item in list(procs):
            if p.poll() is not None:
                procs.remove((p, item))
                done += 1
                out = p.stdout.read().strip().splitlines()
                line = out[-1] if out else ""
                print(f"({done}/{len(work)}) {line}", flush=True)
                if p.returncode != 0:
                    fails += 1
        time.sleep(0.5)
    print(f"dry-run complete: {done - fails} ok, {fails} failed")
    report(out_dir)
    sys.exit(1 if fails else 0)


def report(out_dir):
    rows = []
    for fn in sorted(os.listdir(out_dir)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(out_dir, fn)) as f:
            rows.append(json.load(f))
    print(f"\n=== dry-run report ({len(rows)} cells) ===")
    hdr = (f"{'arch':22s} {'shape':14s} {'mesh':8s} {'status':8s} "
           f"{'GFLOPs':>9s} {'GB':>8s} {'collGB':>8s} {'dom':>10s} "
           f"{'tempGB/dev':>10s}")
    print(hdr)
    for r in rows:
        if r["status"] == "ok":
            print(
                f"{r['arch']:22s} {r['shape']:14s} {r['mesh']:8s} ok       "
                f"{r['hlo_flops'] / 1e9:9.1f} {r['hlo_bytes'] / 1e9:8.2f} "
                f"{r['collective_bytes'] / 1e9:8.3f} "
                f"{r['roofline']['dominant']:>10s} "
                f"{r['memory']['temp_bytes'] / 1e9:10.2f}"
            )
        else:
            why = r.get("reason", r.get("error", ""))[:60]
            print(f"{r['arch']:22s} {r['shape']:14s} {r['mesh']:8s} "
                  f"{r['status']:8s} {why}")


if __name__ == "__main__":
    main()
