import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Everything below may import jax. The dry-run needs 512 placeholder host
# devices so jax.make_mesh can build the production meshes; this env var
# must be set before jax initializes its backends (hence lines 1-2).

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    """Bytes of the first shape literal in `text` (handles tuples by sum)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str):
    """Sum per-device payload bytes of every collective in optimized HLO."""
    out = {k: {"count": 0, "bytes": 0, "max_group": 1} for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if not ls or ls.startswith("//"):
            continue
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[a-z0-9]+\[.*)", ls)
        if m is None:
            continue
        opm = re.search(r"\s((?:all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-permute)(?:-start)?)\(", ls)
        if opm is None:
            continue
        op = opm.group(1).replace("-start", "")
        # output shape(s) are at the head of the rhs
        rhs = m.group(1)
        head = rhs.split(op)[0]
        nbytes = _shape_bytes(head)
        g = 1
        gm = _GROUPS_RE.search(ls)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(ls)
            if gi:
                g = int(gi.group(2))
        rec = out[op]
        rec["count"] += 1
        rec["bytes"] += nbytes
        rec["max_group"] = max(rec["max_group"], g)
    return out


def _mem_dict(mem) -> dict:
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes", "peak_memory_in_bytes")
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def count_params(shapes_tree) -> int:
    import jax
    import numpy as np
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(shapes_tree)))


def active_param_count(cfg, params_tree) -> int:
    """Total params minus the inactive expert fraction (MoE)."""
    import jax
    import numpy as np
    total = count_params(params_tree)
    if cfg.moe is None:
        return total
    inactive = 0
    frac = 1.0 - cfg.moe.experts_per_token / cfg.moe.num_experts
    def visit(path, leaf):
        nonlocal inactive
        names = [getattr(k, "key", None) for k in path]
        if "mlp" in names and any(n in ("wi", "wg", "wo") for n in names):
            if leaf.ndim == 3 or (len(names) > names.index("mlp") + 1
                                  and leaf.ndim >= 3):
                inactive += int(np.prod(leaf.shape) * frac)
    jax.tree_util.tree_map_with_path(visit, params_tree)
    return total - inactive


def run_cell(arch: str, shape: str, mesh_kind: str, approx: str,
             out_dir: str, save_hlo: bool = False, variant: str = "",
             seq_shard: bool = False, vocab_pad: int = 1,
             fast_emul: bool = False, attn_chunk: int = 0,
             mla_absorbed: bool = False, microbatches: int = 1,
             moe_shardmap: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.input_specs import batch_specs
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import (cache_shapes, make_decode_step,
                                    make_prefill_step, make_train_step,
                                    params_shapes, state_shapes)
    from repro.numerics.approx_ops import make_numerics
    from repro.optim.adamw import AdamWConfig
    from repro.sharding import rules as R

    import dataclasses

    t0 = time.time()
    cfg = get_config(arch)
    if approx != "off":
        cfg = cfg.with_approx(make_numerics(approx, "residual",
                                            fast=fast_emul))
    if seq_shard:
        cfg = dataclasses.replace(cfg, seq_shard=True)
    if vocab_pad > 1:
        cfg = dataclasses.replace(cfg, vocab_pad_multiple=vocab_pad)
    if attn_chunk:
        cfg = dataclasses.replace(cfg, attn_kv_chunk=attn_chunk)
    if moe_shardmap and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, use_shard_map=True))
    if mla_absorbed and cfg.mla is not None:
        cfg = dataclasses.replace(
            cfg, mla=dataclasses.replace(cfg.mla, decode_mode="absorbed"))
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    kind, specs, seq = batch_specs(cfg, shape)
    opt_cfg = AdamWConfig()
    ba = R.batch_axes(mesh)

    with mesh:
        if kind == "train":
            st_shapes = state_shapes(cfg, opt_cfg)
            st_shard = R.state_shardings(st_shapes, mesh)
            b_shard = R.data_sharding(specs, mesh)
            fn = make_train_step(cfg, opt_cfg, batch_axes=ba,
                                 microbatches=microbatches, mesh=mesh)
            jfn = jax.jit(fn, in_shardings=(st_shard, b_shard),
                          donate_argnums=(0,))
            lowered = jfn.lower(st_shapes, specs)
        elif kind == "prefill":
            p_shapes = params_shapes(cfg)
            p_shard = R.tree_shardings(p_shapes, mesh, R.PARAM_RULES)
            b_shard = R.data_sharding(specs, mesh)
            fn = make_prefill_step(cfg, seq, batch_axes=ba)
            jfn = jax.jit(fn, in_shardings=(p_shard, b_shard))
            lowered = jfn.lower(p_shapes, specs)
        else:  # decode
            p_shapes = params_shapes(cfg)
            p_shard = R.tree_shardings(p_shapes, mesh, R.PARAM_RULES)
            bsz = specs["tokens"].shape[0]
            c_shapes = cache_shapes(cfg, bsz, seq)
            c_shard = R.cache_shardings(c_shapes, mesh)
            b_shard = R.data_sharding(specs, mesh)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            fn = make_decode_step(cfg, batch_axes=ba)
            jfn = jax.jit(
                fn, in_shardings=(p_shard, b_shard["tokens"],
                                  jax.sharding.NamedSharding(
                                      mesh, jax.sharding.PartitionSpec()),
                                  c_shard),
                donate_argnums=(3,))
            lowered = jfn.lower(p_shapes, specs["tokens"], pos, c_shapes)

        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()

    mem = compiled.memory_analysis()
    print(mem)  # proves it fits (bytes are per device)
    cost = compiled.cost_analysis()
    print({k: cost.get(k) for k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()
    # XLA's cost_analysis covers only the ENTRY computation (scan bodies
    # excluded); the full-graph analyzer walks the call graph with loop
    # trip counts (see launch/hlo_cost.py).
    from repro.launch.hlo_cost import analyze as full_analyze
    totals = full_analyze(hlo)
    coll = totals.collectives

    p_tree = params_shapes(cfg)
    n_total = count_params(p_tree)
    n_active = active_param_count(cfg, p_tree)
    seqlen, gbatch, _ = __import__("repro.configs", fromlist=["SHAPES"]).SHAPES[shape]
    tokens = gbatch * (1 if kind == "decode" else seqlen)
    model_flops = (6 if kind == "train" else 2) * n_active * tokens

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "kind": kind,
        "approx": approx, "variant": variant,
        "devices": int(mesh.devices.size),
        "seq": seq, "tokens": tokens,
        "params_total": n_total, "params_active": n_active,
        "model_flops": float(model_flops),
        "hlo_flops_per_device": float(totals.flops),
        "hlo_bytes_per_device": float(totals.bytes),
        "entry_flops_per_device": float(cost.get("flops", -1)),
        "entry_bytes_per_device": float(cost.get("bytes accessed", -1)),
        "memory": _mem_dict(mem),
        "collectives": coll,
        "dots_top": sorted(totals.dots, key=lambda t: -t[1] * t[2])[:20],
        "lower_s": t_lower - t0, "compile_s": t_compile - t_lower,
        "hlo_chars": len(hlo),
    }
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape}__{mesh_kind}__{approx}" + (
        f"__{variant}" if variant else "")
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    if save_hlo:
        with open(os.path.join(out_dir, tag + ".hlo.txt"), "w") as f:
            f.write(hlo)
    print(json.dumps({k: rec[k] for k in
                      ("arch", "shape", "mesh", "compile_s")}))
    return rec


def orchestrate(args):
    """Run every cell in its own subprocess (jax device-count isolation)."""
    from repro.configs import cells
    meshes = args.meshes.split(",")
    todo = [(a, s) for a, s in cells()
            if (not args.archs or a in args.archs.split(","))
            and (not args.shapes or s in args.shapes.split(","))]
    results = []
    for mesh_kind in meshes:
        for arch, shape in todo:
            tag = f"{arch}__{shape}__{mesh_kind}__{args.approx}"
            path = os.path.join(args.out, tag + ".json")
            if args.resume and os.path.exists(path):
                print(f"[skip existing] {tag}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                   "--approx", args.approx, "--out", args.out]
            print(f"[dryrun] {tag}", flush=True)
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout)
            ok = r.returncode == 0
            results.append((tag, ok, time.time() - t0))
            if not ok:
                err = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                       "approx": args.approx, "error": r.stderr[-4000:]}
                with open(os.path.join(args.out, tag + ".ERROR.json"),
                          "w") as f:
                    json.dump(err, f, indent=1)
                print(r.stderr[-2000:], flush=True)
            print(f"[{'ok' if ok else 'FAIL'}] {tag} "
                  f"({time.time() - t0:.0f}s)", flush=True)
    good = sum(1 for _, ok, _ in results if ok)
    print(f"dry-run sweep: {good}/{len(results)} cells succeeded")
    return 0 if good == len(results) else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=("single", "multi"))
    ap.add_argument("--approx", default="haloc_axa")
    ap.add_argument("--out", default="experiments/artifacts")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--variant", default="", help="artifact tag suffix")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--vocab-pad", type=int, default=1)
    ap.add_argument("--fast-emul", action="store_true")
    ap.add_argument("--attn-chunk", type=int, default=0)
    ap.add_argument("--mla-absorbed", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--moe-shardmap", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", default="")
    ap.add_argument("--shapes", default="")
    ap.add_argument("--meshes", default="single,multi")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()
    if args.all:
        sys.exit(orchestrate(args))
    try:
        run_cell(args.arch, args.shape, args.mesh, args.approx, args.out,
                 save_hlo=args.save_hlo, variant=args.variant,
                 seq_shard=args.seq_shard, vocab_pad=args.vocab_pad,
                 fast_emul=args.fast_emul, attn_chunk=args.attn_chunk,
                 mla_absorbed=args.mla_absorbed,
                 microbatches=args.microbatches,
                 moe_shardmap=args.moe_shardmap)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
