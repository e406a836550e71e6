"""Put a trace's device time down to the program's own layers.

The program names its layers with ``jax.named_scope`` (``repro.obs.names``:
``resnet.s0b0``, ``cim.patches``, ``cim.kernel``, ...). A scope is
trace-time metadata: it lands in the compiled HLO's
``metadata={op_name="jit(f)/resnet.s0b0/conv1/cim.patches/pad"}`` of
every instruction traced inside it (a fusion takes its root's). A device
trace names each op by its HLO instruction (``trace.op_name``), so the
compiled text of the cell's unit maps the trace's ops onto scope paths:

- ``scope_map(parse(text))``: {instruction name -> scope path}, the path being
  the op_name's components less ``jit(...)``-style wrappers and the final
  primitive (``resnet.s0b0/conv1/cim.patches``). XLA drops the op_name of
  some instructions it makes itself (loops it builds for a relayout, the
  later links of a concatenation, async copies); such an instruction
  takes the scope that the instructions of the computations it calls
  share, else its operands', else its users', else that of the loop or
  branch it runs in. Instructions with no scope map to "".
- ``attribute(ops, text)``: the traced window's ``Reduced.ops`` summed by
  scope path. An op that runs inside a loop or a called computation is
  left out where the instruction that calls it has an event of its own
  (that event already holds its time). Op names that the text does not
  hold are reported, not dropped.

``patch_ms`` is the reading behind ``patch_ms.img``: device milliseconds
per unit of work in ops under ``cim.patches`` or ``cim.layout``.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import re
import sys
import time
from typing import Dict, List, Optional, Tuple

from perfbench import loader, traffic

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_REF = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(calls|body|condition|to_apply|true_computation|"
                    r"false_computation|branch_computations)=")
_WRAPPER = re.compile(r"^[\w\-]*\(.*\)$")          # jit(f), jvp(g), ...
#: called computations whose instructions run as ops of their own on the
#: device (a fusion's do not)
_CONTROL = ("body", "condition", "to_apply", "true_computation",
            "false_computation", "branch_computations")
UNSCOPED = ""


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def scope_path(op_name: str) -> str:
    """``jit(f)/resnet.s0b0/conv1/jit(g)/cim.patches/pad`` ->
    ``resnet.s0b0/conv1/cim.patches``. XLA joins the op_names of merged
    instructions with ``;``: the longest path among them wins."""
    best: List[str] = []
    for alt in op_name.split(";"):
        parts = [p for p in alt.split("/") if p and not _WRAPPER.match(p)]
        parts = parts[:-1]                       # the primitive
        if len(parts) > len(best):
            best = parts
    return "/".join(best)


@dataclasses.dataclass
class _Instr:
    computation: str
    own: str                        # scope path of its own op_name, or ""
    operands: List[str]
    called: List[Tuple[str, str]]   # (attribute, computation)


@dataclasses.dataclass
class Hlo:
    """The instructions of a compiled HLO text, by name, and each
    computation's instruction names."""
    instrs: Dict[str, _Instr]
    members: Dict[str, List[str]]


def parse(text: str) -> Hlo:
    instrs: Dict[str, _Instr] = {}
    members: Dict[str, List[str]] = collections.defaultdict(list)
    comp = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        body = line[m.end():]
        op = _OP_NAME.search(body)
        head = body.split(", metadata=", 1)[0]
        called = []
        for cm in _CALLS.finditer(head):
            tail = head[cm.end():]
            group = (tail[1:tail.index("}")] if tail.startswith("{")
                     else tail.split(",", 1)[0])
            called += [(cm.group(1), c) for c in _REF.findall(group)]
        callees = {c for _, c in called}
        instrs[m.group(1)] = _Instr(
            comp, scope_path(op.group(1)) if op else UNSCOPED,
            [r for r in _REF.findall(head) if r not in callees], called)
        members[comp].append(m.group(1))
    return Hlo(instrs, dict(members))


def scope_map(hlo: Hlo) -> Dict[str, str]:
    """{instruction name -> scope path} over every computation (see the
    module docstring for instructions without an op_name of their own):
    an instruction's own scope, else the commonest among the
    instructions of the computations it calls, else the nearest
    producer's up its operands, else the nearest consumer's, breadth
    first; one still without a scope inside a loop body or a branch
    takes the loop's or the branch's."""
    instrs, members = hlo.instrs, hlo.members
    direct: Dict[str, str] = {}

    def own_or_called(name: str) -> str:
        if name not in direct:
            direct[name] = UNSCOPED          # guards against cycles
            ins = instrs[name]
            path = ins.own
            if not path:
                paths = collections.Counter(
                    p for _, comp in ins.called
                    for member in members.get(comp, ())
                    if (p := own_or_called(member)))
                path = paths.most_common(1)[0][0] if paths else UNSCOPED
            direct[name] = path
        return direct[name]

    users: Dict[str, List[str]] = collections.defaultdict(list)
    for name, ins in instrs.items():
        for o in ins.operands:
            users[o].append(name)

    def nearest(name: str, step) -> str:
        seen, queue = {name}, collections.deque([name])
        while queue:
            cur = queue.popleft()
            path = own_or_called(cur)
            if path:
                return path
            for nxt in step(cur):
                if nxt in instrs and nxt not in seen:
                    seen.add(nxt)
                    queue.append(nxt)
        return UNSCOPED

    out = {name: (nearest(name, lambda n: instrs[n].operands)
                  or nearest(name, users.__getitem__))
           for name in instrs}
    inside_of = callers(hlo)
    for name, path in out.items():
        up = inside_of.get(name)
        while not path and up is not None:
            path, up = out[up], inside_of.get(up)
        out[name] = path
    return out


def callers(hlo: Hlo) -> Dict[str, str]:
    """{instruction name -> the instruction whose loop body, branch or
    called computation holds it}, for instructions that run as device
    ops of their own inside another's."""
    out = {}
    for name, ins in hlo.instrs.items():
        for attr, comp in ins.called:
            if attr in _CONTROL:
                for member in hlo.members.get(comp, ()):
                    out[member] = name
    return out


@dataclasses.dataclass
class Attribution:
    by_scope: Dict[str, float]     # scope path -> device seconds
    unmapped: Dict[str, float]     # op names the compiled text lacks
    nested_s: float                # left out: inside a traced caller
    mapped_s: float                # ops found in the text (not nested)
    ops: Dict[str, Tuple[float, str, Optional[str]]]
    # op name -> (seconds, scope path, the traced caller it runs inside)

    @property
    def scoped_s(self) -> float:
        return sum(s for p, s in self.by_scope.items() if p != UNSCOPED)

    def under(self, *scopes: str) -> float:
        """Seconds of ops whose path holds any of ``scopes``."""
        want = set(scopes)
        return sum(s for p, s in self.by_scope.items()
                   if want & set(p.split("/")))


def attribute(ops: Dict[str, float], text: str) -> Attribution:
    hlo = parse(text)
    smap, inside_of = scope_map(hlo), callers(hlo)
    by_scope: Dict[str, float] = collections.Counter()
    unmapped: Dict[str, float] = {}
    found = {}
    nested = mapped = 0.0
    for name, sec in ops.items():
        if name not in smap:
            unmapped[name] = sec
            continue
        up = inside_of.get(name)
        while up is not None and up not in ops:
            up = inside_of.get(up)
        found[name] = (sec, smap[name], up)
        if up is not None:
            nested += sec
        else:
            mapped += sec
            by_scope[smap[name]] += sec
    return Attribution(dict(by_scope), unmapped, nested, mapped, found)


def layer_of(path: str) -> str:
    """The innermost ``cim.*`` scope of a path, else ``model``: the CIM
    dispatch layer's parts against everything else."""
    cim = [p for p in path.split("/") if p.startswith("cim.")]
    return cim[-1] if cim else ("model" if path else "(unscoped)")


def describe(att: Attribution, top: int = 10) -> List[str]:
    """Log lines: the top ops and every CIM kernel with their scopes, the
    top scopes by device seconds, the CIM parts, the share the scopes
    account for, and what they do not."""
    lines = []
    ranked = sorted(att.ops.items(), key=lambda kv: -kv[1][0])
    for i, (name, (sec, path, up)) in enumerate(ranked):
        if i < top or name.startswith("cim_"):
            lines.append(f"op {name} {sec:.6g}s: {path or '(unscoped)'}"
                         + (f" (inside {up})" if up else ""))
    total = att.mapped_s
    for path, sec in sorted(att.by_scope.items(), key=lambda kv: -kv[1])[
            :top]:
        lines.append(f"device_scopes {path or '(unscoped)'}: {sec:.6g}s")
    parts = collections.Counter()
    for path, sec in att.by_scope.items():
        parts[layer_of(path)] += sec
    lines.append("device time by part: " + ", ".join(
        f"{k} {v:.6g}s" for k, v in parts.most_common()))
    lines.append(
        f"scopes account for {att.scoped_s:.6g}s of the module's "
        f"{total:.6g}s on the device "
        f"({100 * att.scoped_s / total if total else 0:.3f}%); "
        f"{att.nested_s:.6g}s inside traced loops left out")
    if att.by_scope.get(UNSCOPED):
        lines.append(f"unscoped: {att.by_scope[UNSCOPED]:.6g}s")
    if att.unmapped:
        worst = sorted(att.unmapped.items(), key=lambda kv: -kv[1])[:top]
        lines.append(f"not in the compiled text ({len(att.unmapped)} op "
                     f"names, {sum(att.unmapped.values()):.6g}s): "
                     + ", ".join(f"{n} {s:.3g}s" for n, s in worst))
    return lines


#: below this share of the window's op time found in the compiled text,
#: the text is not the program that ran, and nothing is read from it
MIN_FOUND = 0.9


def patch_ms(ctx, rate: str, text_of) -> Optional[float]:
    """Device milliseconds per unit in ops under ``cim.patches`` or
    ``cim.layout`` (the program's scopes, ``repro.obs.names``).

    ``text_of()`` returns the compiled text of the unit's program; it is
    called only for a traced run of the rate's cell whose program
    declares the scopes. None where there is nothing to read."""
    if ctx.rate != rate or ctx.trace is None or ctx.units <= 0:
        return None
    from repro.obs import names
    scopes = [getattr(names, n, None) for n in ("SCOPE_PATCHES",
                                                 "SCOPE_LAYOUT")]
    if None in scopes:
        log("the program declares no patch and layout scopes")
        return None
    t0 = time.perf_counter()
    text = text_of()
    t1 = time.perf_counter()
    att = attribute(ctx.trace.ops, text)
    log(f"compiled text of the unit: {len(text)} characters in "
        f"{t1 - t0:.2f}s, scope map and attribution in "
        f"{time.perf_counter() - t1:.2f}s")
    for line in describe(att):
        log(line)
    found = att.mapped_s + att.nested_s
    every = found + sum(att.unmapped.values())
    if every <= 0 or found / every < MIN_FOUND:
        log(f"only {found:.6g}s of {every:.6g}s of the window's ops are in "
            f"the compiled text: not the program that ran")
        return None
    return 1e3 * att.under(*scopes) / ctx.units


def unit_text(metric: str) -> str:
    """The compiled text of the unit of the one cell that lists
    ``metric``, from ``configs/<config>.hlo.py``."""
    bench = loader.benchmark()
    (cell,) = next(m for m in bench["per_layer"]
                   if m["name"] == metric)["workloads"]
    wl = loader.workload(bench, cell)
    mod = loader.load_module(os.path.join(loader.HERE, "configs",
                                          f"{wl['config']}.hlo.py"))
    return mod.compiled_text(loader.sizes(bench, wl["config"]),
                             traffic.load_mix(wl["traffic"]))
