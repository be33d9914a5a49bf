import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

Proves the distribution config is coherent without hardware: ``jax.jit``
with the baseline shardings must lower AND compile for the 16x16 single-pod
mesh and the 2x16x16 multi-pod mesh, for every assigned architecture and
input shape.  Emits per-pair JSON artifacts (memory analysis, cost analysis,
per-collective byte counts parsed from the partitioned HLO) that
``benchmarks/roofline.py`` turns into the §Roofline table.

Usage:
    python -m repro.launch.dryrun --arch phi3-mini-3.8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/dryrun]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from repro.configs.registry import (ARCHS, all_pairs, get_config, get_shape,  # noqa: E402
                                    pair_supported)
from repro.launch import specs as S  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import step_for  # noqa: E402

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.+?)\s+[\w\-]+\(")
_OPND_RE = re.compile(r"(%[\w.\-]+)")
_OP_RE = re.compile(r"=\s*(?:\([^=]*?\)|\S+)\s+([a-z0-9\-]+)(?:-start)?\(")


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum operand bytes of every collective op in (partitioned) HLO text.

    This HLO dialect does not print operand types inline, so first build an
    SSA-name -> result-shape-bytes map from every defining line, then charge
    each collective op the sum of its operands' bytes.  (Per-device program:
    shapes are already the post-SPMD shards.)
    """
    sizes: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            sizes[m.group(1)] = sum(
                _shape_bytes(s) for s in _SHAPE_RE.finditer(m.group(2)))
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = _OP_RE.search(stripped)
        if not m:
            continue
        op = m.group(1)
        if op.endswith("-done"):
            continue
        base = op[:-6] if op.endswith("-start") else op
        if base not in _COLLECTIVES:
            continue
        args = stripped[m.end():]
        depth, end = 1, len(args)
        for i, ch in enumerate(args):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        out[base] += sum(sizes.get(name, 0)
                         for name in _OPND_RE.findall(args[:end]))
    return out


def _compile_pair(cfg, shape, mesh, unroll: bool, fsdp: bool | None = None):
    step = step_for(cfg, shape.kind, unroll=unroll)
    args = S.input_specs(cfg, shape)
    shardings = S.to_shardings(S.input_pspecs(cfg, shape, mesh, fsdp=fsdp),
                               mesh)
    order = list(args.keys())
    t0 = time.perf_counter()
    with mesh:
        jitted = jax.jit(step, in_shardings=tuple(shardings[k] for k in order))
        lowered = jitted.lower(*(args[k] for k in order))
        compiled = lowered.compile()
    return compiled, time.perf_counter() - t0


def _mem_dict(compiled) -> dict:
    mem = compiled.memory_analysis()
    return {"argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0))}


def _cost_dict(compiled) -> dict:
    cost = compiled.cost_analysis()
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
           "transcendentals": float(cost.get("transcendentals", 0.0))}
    out["collective_bytes"] = collective_bytes(compiled.as_text())
    return out


def reduced_depth(cfg, k: int):
    """Same config with k repeating units (remainder layers kept)."""
    import dataclasses
    u = cfg.unit_layers
    rem = cfg.num_layers % u
    upd = {"num_layers": u * k + rem}
    if cfg.encoder_layers:
        upd["encoder_layers"] = k
    return dataclasses.replace(cfg, **upd)


def _extrapolate(c2: dict, c4: dict, n_units: int) -> dict:
    """Linear-in-units extrapolation of per-device cost terms.

    XLA's cost analysis counts while-loop bodies once, so the full-depth
    scan lowering undercounts; instead we lower the model UNROLLED at 2 and
    4 units (cheap to compile), take the exact per-unit delta, and
    extrapolate: cost(L) = cost(4u) + (n_units - 4)/2 * (cost(4u) - cost(2u)).
    Unit costs stack exactly linearly (verified in tests on 2/4/6 units).
    """
    scale = (n_units - 4) / 2.0

    def ext(a, b):
        return max(0.0, b + scale * (b - a))

    out = {k: ext(c2[k], c4[k]) for k in ("flops", "bytes_accessed",
                                          "transcendentals")}
    out["collective_bytes"] = {
        k: int(ext(c2["collective_bytes"][k], c4["collective_bytes"][k]))
        for k in c4["collective_bytes"]}
    return out


def dryrun_pair(arch: str, shape_name: str, multi_pod: bool = False,
                verbose: bool = True, with_cost: bool = True,
                cfg_overrides: dict | None = None) -> dict:
    """Lower + compile one (arch, shape, mesh); return the roofline artifact.

    Three lowerings: the full-depth scan program (the production program —
    its compile success is the dry-run gate, its memory analysis has real
    buffer reuse) and, for single-pod cost accounting, two reduced-depth
    unrolled programs whose per-unit cost delta extrapolates to full depth.

    ``cfg_overrides``: dataclasses.replace overrides for §Perf variants
    (e.g. {"vocab_pad_multiple": 128}).
    """
    import dataclasses as _dc
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = get_shape(shape_name)
    ok, reason = pair_supported(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skip", "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    fsdp = S.needs_fsdp(cfg, shape.kind, mesh)
    compiled, t_full = _compile_pair(cfg, shape, mesh, unroll=False,
                                     fsdp=fsdp)
    art = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "chips": mesh.size, "kind": shape.kind,
        "fsdp": fsdp, "compile_s": round(t_full, 2),
        "memory": _mem_dict(compiled),
    }
    if with_cost and not multi_pod:
        c2, t2 = _compile_pair(reduced_depth(cfg, 2), shape, mesh,
                               unroll=True, fsdp=fsdp)
        c4, t4 = _compile_pair(reduced_depth(cfg, 4), shape, mesh,
                               unroll=True, fsdp=fsdp)
        n_units = cfg.num_layers // cfg.unit_layers
        art["cost"] = _extrapolate(_cost_dict(c2), _cost_dict(c4), n_units)
        art["cost_compile_s"] = round(t2 + t4, 2)
        art["collective_total"] = int(
            sum(art["cost"]["collective_bytes"].values()))
    if verbose:
        mb = art["memory"]
        msg = (f"{arch:26s} {shape_name:12s} pods={2 if multi_pod else 1} "
               f"compile={t_full:.1f}s arg={mb['argument_bytes']/1e9:.2f}GB "
               f"temp={mb['temp_bytes']/1e9:.2f}GB")
        if "cost" in art:
            msg += (f" flops={art['cost']['flops']:.3g} "
                    f"coll={art['collective_total']/1e6:.1f}MB")
        print(msg, flush=True)
    return art


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    pairs = all_pairs() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in pairs:
        for mp in meshes:
            try:
                art = dryrun_pair(arch, shape, multi_pod=mp)
            except Exception as e:  # a failure here is a bug in our sharding
                art = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "error", "error": f"{type(e).__name__}: {e}"}
                failures.append(art)
                print(f"FAIL {arch} {shape} multi_pod={mp}: {e}")
            fn = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}.json"
            with open(os.path.join(args.out, fn), "w") as f:
                json.dump(art, f, indent=1)
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures")
    print("all dry-runs OK")


if __name__ == "__main__":
    main()
