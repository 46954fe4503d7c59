"""Each traced device operation's layer, from its compiled module's HLO text.

The profiler names a device operation by its HLO instruction (``fusion.7``);
``compiled.as_text()`` gives every instruction's metadata: the name stack it
was traced under (``op_name``, with any ``jax.named_scope``) and its Python
stack (``stack_frame_id`` into the module's tables of files, lines and
frames). A fusion is given the metadata of the first matrix product or
convolution inside it, else its own.

An operation's layer is, in order:

1. the innermost name of its name stack that is one of ``scopes`` (named
   scopes the program puts round a layer; a name inside a transform's
   parentheses, ``transpose(jvp(mlp))``, counts);
2. else the first of its stack frames, innermost first, whose file and
   enclosing function (``Class.method`` or ``function``, nested functions
   counted in the function that defines them) match a ``Rule``;
3. else the first ``Rule`` whose name-stack component the name stack holds
   (an operation JAX gave no frame, such as a loop's own bookkeeping);
4. else ``other``.

Its pass is ``bwd`` where the name stack holds ``transpose(`` (gradients,
and the forward work recomputed for them), ``fwd`` where it holds ``jvp(``,
and ``none`` otherwise (the optimizer, outside the differentiated function).

JAX writes name stacks and whole stacks into the metadata only with
``jax_include_full_tracebacks_in_locations`` on; ``run.py`` turns it on.
"""
from __future__ import annotations

import ast
import dataclasses
import functools
import re
from typing import Dict, Iterable, Optional, Sequence, Tuple

OTHER = "other"

_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_META = re.compile(r'metadata=\{((?:[^{}"]|"(?:[^"\\]|\\.)*")*)\}')
_FIELD = re.compile(r'(\w+)=("(?:[^"\\]|\\.)*"|[^\s}]+)')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_PRODUCT = re.compile(r"\s(?:convolution|dot)\(")


@dataclasses.dataclass(frozen=True)
class Rule:
    """Operations with a frame in a file ending in ``file`` and, unless
    ``functions`` is empty, in one of ``functions`` belong to ``layer``; or,
    for a rule with a ``component`` instead, operations whose name stack
    holds that component."""
    layer: str
    file: str = ""
    functions: Tuple[str, ...] = ()
    component: str = ""

    def matches(self, path: str, function: str) -> bool:
        return bool(self.file) and path.endswith(self.file) and (
            not self.functions or function in self.functions)


@dataclasses.dataclass(frozen=True)
class Op:
    op_name: str
    frames: Tuple[Tuple[str, int], ...]      # (file, line), innermost first

    @property
    def pass_(self) -> str:
        if "transpose(" in self.op_name:
            return "bwd"
        return "fwd" if "jvp(" in self.op_name else "none"


def _fields(meta: str) -> Dict[str, str]:
    return {k: v[1:-1] if v.startswith('"') else v
            for k, v in _FIELD.findall(meta)}


def _tables(text: str) -> Dict[str, Dict[int, Dict[str, str]]]:
    out: Dict[str, Dict[int, Dict[str, str]]] = {t: {} for t in _TABLES}
    current = None
    for line in text.splitlines():
        if line in _TABLES:
            current = out[line]
        elif current is not None and line and line[0].isdigit():
            key, rest = line.split(" ", 1)
            rest = rest.strip()
            current[int(key)] = (_fields(rest[1:-1]) if rest.startswith("{")
                                 else {"name": rest.strip('"')})
        else:
            current = None
    return out


def _frames(tables, frame_id: int) -> Tuple[Tuple[str, int], ...]:
    """(file, line) of a stack frame and its callers, innermost first. A
    frame's ``parent_frame_id`` counts from 1 for no parent."""
    frames, seen = [], set()
    while frame_id > 0 and frame_id not in seen:
        seen.add(frame_id)
        frame = tables["StackFrames"].get(frame_id)
        if frame is None:
            break
        loc = tables["FileLocations"].get(int(frame["file_location_id"]), {})
        name = tables["FileNames"].get(int(loc.get("file_name_id", 0)), {})
        if "name" in name:
            frames.append((name["name"], int(loc["line"])))
        frame_id = int(frame.get("parent_frame_id", 0)) - 1
    return tuple(frames)


def parse(text: str) -> Dict[str, Op]:
    """Instruction name -> its ``Op``, for every instruction of an HLO
    module's text that carries metadata or fuses a product that does."""
    tables = _tables(text)
    meta: Dict[str, str] = {}                 # instruction -> metadata
    calls: Dict[str, str] = {}                # fusion -> fused computation
    products = set()                          # dot and convolution
    computations: Dict[str, list] = {}
    current: Optional[list] = None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.endswith("{"):
            head = line.split("(", 1)[0].split()
            current = computations.setdefault(head[-1].lstrip("%"), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR.match(line)
        if not m or current is None:
            continue
        name, rest = m.groups()
        current.append(name)
        found = _META.search(rest)
        if found:
            meta[name] = found.group(1)
        if " fusion(" in rest and _CALLS.search(rest):
            calls[name] = _CALLS.search(rest).group(1)
        if _PRODUCT.search(rest):
            products.add(name)
    ops: Dict[str, Op] = {}
    for name in set(meta) | set(calls):
        source = meta.get(name, "")
        for inner in computations.get(calls.get(name, ""), ()):
            if inner in products and inner in meta:
                source = meta[inner]
                break
        f = _fields(source)
        frame = int(f.get("stack_frame_id", 0))
        ops[name] = Op(f.get("op_name", ""),
                       _frames(tables, frame) if frame else ())
    return ops


@functools.lru_cache(maxsize=None)
def _functions(path: str) -> Tuple[Tuple[int, int, str], ...]:
    """(first line, last line, name) of each function a module defines at
    its top level or in a class at its top level."""
    try:
        tree = ast.parse(open(path, encoding="utf-8").read())
    except (OSError, SyntaxError, ValueError):
        return ()
    out = []
    for node in tree.body:
        defs = [(node, node.name)] if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef)) else []
        if isinstance(node, ast.ClassDef):
            defs = [(n, f"{node.name}.{n.name}") for n in node.body
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for fn, name in defs:
            first = min([fn.lineno] + [d.lineno for d in fn.decorator_list])
            out.append((first, fn.end_lineno, name))
    return tuple(out)


def function_at(path: str, line: int) -> str:
    """The function whose definition holds ``line`` of ``path``, or
    ``<module>``."""
    for first, last, name in _functions(path):
        if first <= line <= last:
            return name
    return "<module>"


def layer_of(op: Op, scopes: Iterable[str], rules: Sequence[Rule]) -> str:
    # a scope may sit inside a transform's name: jvp(attention)
    parts = [p for p in re.split(r"[/()]", op.op_name) if p]
    scopes = set(scopes)
    for part in reversed(parts):
        if part in scopes:
            return part
    for path, line in op.frames:
        function = function_at(path, line)
        for rule in rules:
            if rule.matches(path, function):
                return rule.layer
    for rule in rules:
        if rule.component and rule.component in parts:
            return rule.layer
    return OTHER


def split(op_s: Dict[str, float], ops: Dict[str, Dict[str, Op]],
          scopes: Iterable[str],
          rules: Sequence[Rule]) -> Dict[Tuple[str, str], float]:
    """Device seconds by (layer, pass) of the traced operations ``op_s``
    (``module:op`` -> seconds, each instant counted once). ``ops`` holds the
    parsed text (``parse``) of the modules whose operations are attributed;
    every other operation, and one the text does not name, is (``other``,
    ``none``)."""
    scopes = tuple(scopes)
    out: Dict[Tuple[str, str], float] = {}
    for key, secs in op_s.items():
        module, _, name = key.rpartition(":")
        op = ops.get(module, {}).get(name)
        where = ((layer_of(op, scopes, rules), op.pass_) if op is not None
                 else (OTHER, "none"))
        out[where] = out.get(where, 0.0) + secs
    return out


PASSES = ("fwd", "bwd", "none")


def ms_per_step(ctx: Dict, layers: Sequence[str],
                passes: Sequence[str] = PASSES) -> Optional[float]:
    """A per-layer metric's reading: device milliseconds a step of
    ``layers`` in ``passes``, from the driver's ``layer_split`` of the
    traced window; None where the trace or the split finds none."""
    trace, split = ctx.get("trace"), getattr(ctx["driver"], "layer_split",
                                             None)
    if trace is None or split is None:
        return None
    by, steps = split(trace)
    secs = sum(v for (layer, p), v in by.items()
               if layer in layers and p in passes)
    return 1e3 * secs / steps if steps and secs > 0 else None
