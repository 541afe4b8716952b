"""Compare the compiled programs of two checkouts, up to what only names
or annotates them.

    python3 tools/compiled_hlo.py dump OUT_DIR [--root CHECKOUT] [CELL ...]
    python3 tools/compiled_hlo.py diff DIR_A DIR_B

``dump`` needs the chip, and exits at once on any other backend: off
the chip the dispatch takes its jnp branches, whose programs hold no
kernel.  For each benchmark cell (all of ``BENCHMARK.json`` by default)
it runs the cell driver's set-up, as a benchmark run does, and writes
the compiled HLO of the callables the window drives (the driver's own):
``<cell>.jit_step.txt`` for a training cell, ``<cell>.jit_meta_probs.txt``
and ``<cell>.jit_predict_topk.txt`` for a decode cell.  Each file's
first line names the device kind it was compiled for.  ``--root`` picks
the checkout whose program and benchmark are compiled (default: the one
holding this file).

``diff`` compares the files two dumps share after ``normalize``, which
removes frontend attributes, op metadata, the source-location tables,
the names of instructions and computations (renumbered in order of
first use) and, inside each Mosaic kernel body, source locations and
the kernel's symbol name.  It prints each difference and exits 1 if
there is any; it refuses (exit 2) a pair of files whose device kinds
differ or are missing.
"""

from __future__ import annotations

import argparse
import base64
import difflib
import hashlib
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KIND = "device_kind: "
SOURCE_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


def _drop_attribute(text: str, key: str) -> str:
    """Remove every ``, key={...}`` (braces balanced, strings skipped)."""
    out, i, marker = [], 0, f", {key}={{"
    while True:
        j = text.find(marker, i)
        if j < 0:
            out.append(text[i:])
            return "".join(out)
        out.append(text[i:j])
        k, depth, quoted = j + len(marker), 1, False
        while depth:
            c = text[k]
            if quoted:
                if c == "\\":
                    k += 1
                elif c == '"':
                    quoted = False
            elif c == '"':
                quoted = True
            elif c == "{":
                depth += 1
            elif c == "}":
                depth -= 1
            k += 1
        i = k


def _drop_source_tables(text: str) -> str:
    out, skipping = [], False
    for line in text.split("\n"):
        if line in SOURCE_TABLES:
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        elif not skipping:
            out.append(line)
    return "\n".join(out)


def _kernel_body(encoded: str) -> str:
    """A Mosaic kernel's serialized module, printed without locations
    and with its symbol name replaced, as a short digest."""
    from jax._src.lib import tpu
    from jaxlib.mlir import ir
    with ir.Context() as ctx, ir.Location.unknown():
        ctx.allow_unregistered_dialects = True
        tpu.register_dialect(ctx)
        module = ir.Module.parse(base64.b64decode(encoded))
        asm = module.operation.get_asm(enable_debug_info=False)
    asm = re.sub(r"^module @\S+", "module @kernel", asm)
    return "mosaic:" + hashlib.sha256(asm.encode()).hexdigest()[:16]


def normalize(text: str) -> str:
    text = _drop_attribute(text, "frontend_attributes")
    text = _drop_attribute(text, "metadata")
    text = _drop_source_tables(text)
    text = re.sub(r'"body":"([^"]+)"',
                  lambda m: f'"body":"{_kernel_body(m.group(1))}"', text)
    names: dict[str, str] = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


def _read(path: str) -> tuple[str, str]:
    """(device kind, compiled text) of a dump file; kind "" if unnamed."""
    with open(path) as f:
        head, _, text = f.read().partition("\n")
    if head.startswith(KIND):
        return head[len(KIND):], text
    return "", head + "\n" + text


def diff(dir_a: str, dir_b: str) -> int:
    shared = sorted(set(os.listdir(dir_a)) & set(os.listdir(dir_b)))
    if not shared:
        print(f"no dump file in both {dir_a} and {dir_b}")
        return 1
    differ = 0
    for name in shared:
        (kind_a, a), (kind_b, b) = (_read(os.path.join(d, name))
                                    for d in (dir_a, dir_b))
        if not kind_a or kind_a != kind_b:
            print(f"{name}: compiled for {kind_a or 'no named device'} and "
                  f"{kind_b or 'no named device'}; not compared")
            return 2
        a, b = normalize(a), normalize(b)
        lines = list(difflib.unified_diff(a.split("\n"), b.split("\n"),
                                          name, name, lineterm="", n=0))
        differ += bool(lines)
        print(f"{name}: {'differs' if lines else 'equal'} "
              f"({len(a.splitlines())} lines)")
        if lines:
            print("\n".join(lines[:40]))
    return 1 if differ else 0


def dump(out_dir: str, root: str, cells: list[str]) -> int:
    for p in (root, os.path.join(root, "src")):
        sys.path.insert(0, p)
    import jax

    from bench import run, spec
    if jax.default_backend() != "tpu":
        raise SystemExit(f"compiled_hlo: dump needs a TPU, JAX found "
                         f"{jax.default_backend()!r}")
    kind = jax.devices()[0].device_kind
    os.makedirs(out_dir, exist_ok=True)
    names = cells or [w["name"] for w in
                      spec.load_json(spec.ROOT / "BENCHMARK.json")
                      ["workloads"]]
    for name in names:
        cell = spec.resolve(name)
        run.configure_jax(cell.config)
        driver = spec.load_module("drivers", cell.kind).Driver(cell, 1)
        driver.setup()
        if cell.kind == "train":
            calls = {"jit_step": driver.step.lower(
                driver.params, driver.state, *driver.inputs[0])}
        else:
            x = driver.inputs[0]
            calls = {"jit_meta_probs": driver.meta.lower(driver.params, x),
                     "jit_predict_topk": jax.jit(driver.topk).lower(
                         driver.meta(driver.params, x), driver.table)}
        for fn, lowered in calls.items():
            path = os.path.join(out_dir, f"{name}.{fn}.txt")
            with open(path, "w") as f:
                f.write(f"{KIND}{kind}\n{lowered.compile().as_text()}")
            print(f"wrote {path}", flush=True)
        driver.release()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("dump")
    d.add_argument("out_dir")
    d.add_argument("cells", nargs="*")
    d.add_argument("--root", default=ROOT)
    c = sub.add_parser("diff")
    c.add_argument("dir_a")
    c.add_argument("dir_b")
    args = ap.parse_args(argv)
    if args.cmd == "dump":
        return dump(args.out_dir, os.path.abspath(args.root), args.cells)
    return diff(args.dir_a, args.dir_b)


if __name__ == "__main__":
    sys.exit(main())
