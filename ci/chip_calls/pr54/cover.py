"""What covers each permute of the layers' scan bodies, from a compiled text.

For each `while` body that holds four or more `collective-permute-start`s
(the forward's and the backward's scan bodies of the four-chip training
step) and each of its permutes, in schedule order: the shard, the pairs
(`fsdp` {{0,2},..} or `tp` {{0,1},..}), how many instructions stand between
its start and its done, which of them are matmul fusions or Mosaic kernels,
and the sum of XLA's own `estimated_cycles` (each fusion's `backend_config`)
over them, in ms at the v5e's 1.5 GHz. Then the two instructions that take a
weight shard's `-done` (the ARRIVED shard) or its start's operand (the OWN
shard) first, by operands and not by name, and each body's summed estimate.
Needs no chip and no jax.

    python ci/chip_calls/pr54/cover.py <compiled.txt> [--json]
    python ci/chip_calls/pr54/cover.py --compile <checkout> <out.txt> [layers]

`--compile` writes the text first: the cell's step (`mistral7b-train-4chip`:
Mistral-7B widths, 22 layers, fsdp 2 x tp 2, batch 2 x 2048) of `<checkout>`,
compiled for the described `v5e:2x2` (needs jax and libtpu, no chip; ~25 s).
"""

from __future__ import annotations

import json
import re
import sys

HZ = 1.5e9  # the v5e's clock, for `estimated_cycles`
_HEAD = re.compile(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$")
_NAME = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_PAIRS = {"{{0,2},{2,0},{1,3},{3,1}}": "fsdp", "{{0,1},{1,0},{2,3},{3,2}}": "tp"}


def computations(hlo: str) -> dict:
    """name -> lines of each computation of a compiled module's text."""
    out, name = {}, None
    for line in hlo.splitlines():
        head = _HEAD.match(line)
        if head:
            name = head.group(1)
            out[name] = []
        elif name is not None and line.strip() != "}":
            out[name].append(line)
    return out


def is_matmul(comps: dict, line: str) -> bool:
    """A fusion whose computation, or one it calls, holds a convolution."""
    called = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
    body = "\n".join(comps.get(called.group(1), [])) if called else ""
    return " convolution(" in body or any(
        " convolution(" in "\n".join(comps.get(c, []))
        for c in re.findall(r"calls=%([\w.\-]+)", body))


def name_of(line: str) -> str:
    m = _NAME.match(line)
    return m.group(1) if m else ""


def shape_of(line: str) -> str:
    m = re.search(r"= \(?(\w+\[[\d,]*\])", line)
    return m.group(1) if m else ""


def operands(line: str) -> list:
    """Names the instruction takes, in order (what it calls is none)."""
    m = re.search(r" [a-z][a-z0-9\-]*\(([^)]*)\)", line)
    return re.findall(r"%([\w.\-]+)", m.group(1)) if m else []


def ms(line: str) -> float:
    m = _CYCLES.search(line)
    return int(m.group(1)) / HZ * 1e3 if m else 0.0


def scan_bodies(comps: dict) -> dict:
    """{"forward": lines, "backward": lines}: the backward's holds more
    permutes (its own exchanges and the weight gradients'). Of the `while`
    bodies (since PR 61 the entry holds the head's permutes too); of a kept
    excerpt, which has no `while`, every computation is looked at."""
    called = {name for lines in comps.values() for l in lines
              for name in re.findall(r" while\(.*body=%([\w.\-]+)", l)}
    bodies = sorted(
        (comps[name] for name in sorted(called or comps)
         if sum(" collective-permute-start(" in l for l in comps[name]) >= 4),
        key=lambda lines: sum(" collective-permute-start(" in l for l in lines))
    if len(bodies) != 2:
        raise ValueError(f"{len(bodies)} scan bodies with permutes, not 2")
    return dict(zip(("forward", "backward"), bodies))


def permutes(comps: dict, body: list) -> list:
    """One row a permute of the body, in schedule order."""
    rows = []
    for i, line in enumerate(body):
        if " collective-permute-start(" not in line:
            continue
        name = name_of(line)
        done = next(j for j, d in enumerate(body)
                    if f"collective-permute-done(%{name})" in d)
        between = body[i + 1:done]
        matmuls = [m for m in between if is_matmul(comps, m)]
        pairs = re.search(r"source_target_pairs=(\{\{[\d,{}]*\}\})", line).group(1)
        rows.append({
            "start": name, "done": name_of(body[done]), "shard": shape_of(line),
            "axis": _PAIRS.get(pairs, pairs), "own": operands(line)[0],
            "at": i, "done_at": done, "between": len(between),
            "matmuls": [[name_of(m), shape_of(m), round(ms(m), 3)] for m in matmuls],
            "kernels": sum("tpu_custom_call" in m for m in between),
            "matmul_ms": round(sum(ms(m) for m in matmuls), 3),
            "between_ms": round(sum(ms(m) for m in between), 3)})
    return rows


def first_products(comps: dict, body: list, row: dict) -> list:
    """The matmul fusions that take the permute's shard, in schedule order:
    [name, shape, "own" | "arrived", estimated ms, schedule index]."""
    out = []
    for i, line in enumerate(body):
        if not is_matmul(comps, line):
            continue
        ops = operands(line)
        which = ("arrived" if row["done"] in ops
                 else "own" if row["own"] in ops else None)
        if which:
            out.append([name_of(line), shape_of(line), which, round(ms(line), 3), i])
    return out


def read(hlo: str) -> dict:
    comps = computations(hlo)
    out = {}
    for side, body in scan_bodies(comps).items():
        rows = permutes(comps, body)
        for row in rows:
            if row["axis"] == "fsdp" and row["shard"].count(",") == 1:
                row["products"] = first_products(comps, body, row)
        out[side] = {"instructions": len(body),
                     "estimated_ms": round(sum(ms(l) for l in body), 3),
                     "matmul_ms": round(sum(ms(l) for l in body
                                            if is_matmul(comps, l)), 3),
                     "permutes": rows}
    return out


def show(report: dict) -> None:
    for side, body in report.items():
        print(f"{side} body: {body['instructions']} instructions, XLA's estimate "
              f"{body['estimated_ms']:.3f} ms a layer ({body['matmul_ms']:.3f} in "
              f"matmul fusions)")
        for r in body["permutes"]:
            cover = (", ".join(f"{n} {s} {t:.3f}" for n, s, t in r["matmuls"])
                     or "NO matmul fusion")
            print(f"  {r['start']:<28}{r['axis']:<5}{r['shard']:<20}"
                  f"{r['between']:>4} between, {r['kernels']} kernel(s), "
                  f"matmul {r['matmul_ms']:.3f} ms of {r['between_ms']:.3f}: {cover}")
            for n, s, which, t, at in r.get("products", []):
                where = ("before the done" if at < r["done_at"] else "behind the done")
                print(f"      {which:<8}{n} {s} {t:.3f} ms, {where}")


def compile_step(checkout: str, out: str, layers: int = 22) -> None:
    import dataclasses
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
    sys.path.insert(0, os.path.abspath(checkout))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from ray_tpu.models.transformer import ModelConfig
    from ray_tpu.ops.pallas import _util
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train.step import batch_sharding, default_optimizer, make_train_step

    _util.on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cfg = dataclasses.replace(
        ModelConfig(vocab_size=32768, d_model=4096, n_layers=layers, n_heads=32,
                    n_kv_heads=8, d_ff=14336, rope_theta=1e6),
        max_seq_len=2048, remat="dots", loss_chunk=0, fused_ffn=False,
        fused_attn=False)
    mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=2), topo.devices[:4])
    step_fn, init_fn, shardings = make_train_step(cfg, mesh, default_optimizer())
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)), shardings)
    b_sh = batch_sharding(mesh)
    batch = {k: jax.ShapeDtypeStruct((2, 2048), jnp.int32, sharding=b_sh[k])
             for k in ("inputs", "targets")}
    compiled = step_fn.lower(state, batch).compile()
    with open(out, "w") as f:
        f.write(compiled.as_text())
    m = compiled.memory_analysis()
    print(f"# {out}: {layers} layers, temp {m.temp_size_in_bytes} B, "
          f"arguments {m.argument_size_in_bytes} B", file=sys.stderr)


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0] == "--compile":
        compile_step(args[1], args[2], int(args[3]) if len(args) > 3 else 22)
        args = [args[2]]
    with open(args[0]) as f:
        report = read(f.read())
    if "--json" in args:
        print(json.dumps(report, indent=1))
    else:
        show(report)
