"""Check that a change to the step programs changed their metadata only:
the optimized HLO of the paged decode dispatch and prefill, compiled for a
described (not attached) TPU v5e at a benchmark configuration's full
size, compared between two checkouts with the metadata stripped.

    python3 benchmarks/hlo_identity.py dump --root <checkout> \\
        --config benchmarks/onchip/configs/qwen3-1.7b.json \\
        --out <dir> --widths 64,32 --prefill 512
    python3 benchmarks/hlo_identity.py compare <dir a> <dir b>

``dump`` imports ``repro`` from ``--root`` (run it once per checkout)
and writes ``dispatch_<width>.hlo`` and ``prefill_<length>.hlo``; no
parameter or pool is allocated.  ``compare`` strips what a
``jax.named_scope`` can change (each instruction's ``metadata={...}``,
the stack-frame tables, and the debug locations inside the Mosaic
kernel bodies, which are compared as MLIR without them) and prints, per
program, whether the two texts and kernel bodies are identical.  It
exits non-zero if any differ.  Runs on the CPU.
"""
import argparse
import base64
import functools
import os
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def dump(args) -> int:
    root = Path(args.root).resolve()
    sys.path[:0] = [str(root / "src"), str(HERE)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from onchip.model import load_spec, program_config
    from repro.engine.engine import Engine
    from repro.models import init_params
    from repro.models.cache import init_paged_cache
    jax.config.update("jax_enable_compilation_cache", False)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    spec = load_spec(Path(args.config))
    cfg = program_config(spec)
    # the step programs read only these fields of an engine
    eng = Engine.__new__(Engine)
    eng.cfg, eng.max_slots, eng.max_seq_len = (cfg, spec.max_slots,
                                               spec.max_seq_len)
    eng.temperature, eng.kernel_mode = 0.0, "pallas"

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def shaped(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    params = shaped(jax.eval_shape(functools.partial(init_params, cfg=cfg),
                                   jax.random.PRNGKey(0)))
    cache = shaped(jax.eval_shape(lambda: init_paged_cache(
        cfg, spec.max_slots, spec.max_seq_len, spec.num_blocks,
        spec.block_size)))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    programs = [(f"dispatch_{w}", eng._decode_dispatch_paged,
                 (sds((w, 1), jnp.int32), sds((w,), bool),
                  sds((2,), jnp.uint32)))
                for w in _ints(args.widths)]
    programs += [(f"prefill_{n}", eng._prefill_paged,
                  (sds((1, n), jnp.int32), sds((), jnp.int32),
                   sds((), jnp.int32)))
                 for n in _ints(args.prefill)]
    for name, fn, rest in programs:
        text = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, *rest).compile().as_text()
        (out / f"{name}.hlo").write_text(text)
        print(f"{name}: {len(text)} characters", flush=True)
    return 0


def _ints(csv: str):
    return [int(x) for x in csv.split(",") if x]


_TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\b")
_METADATA = re.compile(r",? metadata=\{[^}]*\}")
_KERNEL = re.compile(r'(\\?"body\\?":\\?")([A-Za-z0-9+/=]+)')


def _kernel_asm(raw: bytes) -> str:
    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        return ir.Module.parse(raw).operation.get_asm(enable_debug_info=False)


def stripped(text: str):
    """(HLO text without metadata, kernel bodies without debug info)."""
    lines, kernels, table = [], [], False
    for line in text.split("\n"):
        if _TABLES.match(line):
            table = True
            continue
        if table and re.match(r"^\s*\d+ ", line):
            continue
        table = False

        def body(m):
            kernels.append(_kernel_asm(base64.b64decode(m.group(2))))
            return f"{m.group(1)}<kernel {len(kernels) - 1}>"
        lines.append(_KERNEL.sub(body, _METADATA.sub("", line)))
    return "\n".join(lines), kernels


def compare(args) -> int:
    a, b = Path(args.a), Path(args.b)
    names = sorted(p.name for p in a.glob("*.hlo"))
    if not names or names != sorted(p.name for p in b.glob("*.hlo")):
        print(f"the two directories hold different programs: {names}")
        return 1
    same = True
    for name in names:
        (ta, ka), (tb, kb) = (stripped((d / name).read_text())
                              for d in (a, b))
        print(f"{name}: text identical {ta == tb}, {len(ka)} kernels "
              f"identical {ka == kb}")
        same &= ta == tb and ka == kb
    return 0 if same else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("dump")
    d.add_argument("--root", required=True)
    d.add_argument("--config", required=True)
    d.add_argument("--out", required=True)
    d.add_argument("--widths", default="64,32")
    d.add_argument("--prefill", default="512")
    c = sub.add_parser("compare")
    c.add_argument("a")
    c.add_argument("b")
    args = ap.parse_args()
    return dump(args) if args.cmd == "dump" else compare(args)


if __name__ == "__main__":
    sys.exit(main())
