"""Device time per stage of the program's round in a traced run.

The program names each stage of its round with a ``jax.named_scope``
(``repro.core.exec_common.ROUND_STAGES``), which reaches the compiled
module's ``op_name`` metadata.  A v5e trace names an operation by its HLO
text alone, without that metadata, so the stage is joined to the trace by
instruction name through the compiled module's text: ``op_scopes(text)``
maps each instruction to its ``op_name``, and ``scope_time`` sums the own
device seconds (``trace_reduce.reduce``'s ``op_s``) of the operations
inside given scopes, each tag a whole ``/``-delimited component, so that
``fap.horizon`` does not take ``fap.horizon_update``.

A metric gets the run's results (``ctx``) and not its cell.
``stage_share(ctx, tags)`` finds the cell among the live objects (the one
instance of the configuration's entry class), reads the compiled text of
its device call once (``program_text``: the executable the trace ran is in
memory, so this compiles nothing), prints a ``program_text`` line with the
seconds it took, and keeps the map in ``ctx["op_scopes"]`` for the run's
other stage metrics.  Where any of this is missing, as in a program
without the scopes, the share is None.
"""
from __future__ import annotations

import gc
import importlib
import json
import re

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(calls|body|condition|to_apply)=%?([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")


def instr_name(hlo: str) -> str:
    """``fusion.12`` for ``%fusion.12 = f32[8] fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: op_name} over every computation of a compiled
    module's text.  An instruction the compiler made without metadata (the
    float64 rewrite's fusions, layout copies) takes the ``op_name`` of the
    work it stands for: a fusion that of its fused computation's root, or
    else of the first of its instructions that has one; any other that of
    the instruction calling the computation it lives in (a loop's body, a
    branch).  What none of these names maps to ""."""
    own, comp_of, caller, root, first, fused = {}, {}, {}, {}, {}, {}
    cur = None
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " "):
            m = _COMP.match(line)
            cur = m.group(1) if m else None
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        comp_of[name] = cur
        if line.lstrip().startswith("ROOT "):
            root[cur] = name
        if op and cur not in first:
            first[cur] = op.group(1)
        for kind, comp, branches in _CALLED.findall(line):
            for c in [comp] if comp else re.findall(r"[\w.\-]+", branches):
                caller.setdefault(c, name)
            if kind == "calls":
                fused[name] = comp
    memo = {}

    def resolve(name, depth=0):
        if name in memo:
            return memo[name]
        op = own.get(name, "")
        if not op and name in fused:
            c = fused[name]
            op = own.get(root.get(c), "") or first.get(c, "")
        if not op and depth < 64:
            up = caller.get(comp_of.get(name))
            op = resolve(up, depth + 1) if up else ""
        memo[name] = op
        return op

    return {name: resolve(name) for name in own}


def scope_time(reduced: dict, scopes: dict, tags) -> tuple[float, int]:
    """Own device seconds, and events on the first device, of the traced
    operations whose ``op_name`` (``scopes``, from ``op_scopes``) holds one
    of ``tags`` as a whole ``/``-delimited component."""
    tags = set(tags)
    names = [n for n in reduced["op_s"]
             if tags.intersection(scopes.get(instr_name(n), "").split("/"))]
    return (sum(reduced["op_s"][n] for n in names),
            sum(reduced["op_n"].get(n, 0) for n in names))


def scope_share(reduced, scopes, tags):
    """``scope_time`` as a share (%) of the traced busy time, or None where
    the run gave no trace, no module text, or no traced operation in the
    scopes (a program without them)."""
    if reduced is None or not scopes or reduced["busy_s"] <= 0:
        return None
    sec, n = scope_time(reduced, scopes, tags)
    return 100.0 * sec / reduced["busy_s"] if n else None


def program_text(cell) -> str:
    """The compiled module's text of the cell's device call (``kcall``, a
    ``bench.common.hoist``), lowered from shapes, since the warm-up call
    took the example carry's buffers."""
    import jax
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       weak_type=a.weak_type),
        cell.kcall.lower_args)
    return cell.kcall.jitted.lower(*shapes).compile().as_text()


def live_cell(ctx):
    """The run's cell: the one live instance of the configuration's entry
    class (``bench/<entry>.py``'s ``Cell``), or None."""
    cls = importlib.import_module("bench." + ctx["config"]["entry"]).Cell
    gc.collect()
    # by type alone: ``isinstance`` would ask a dead weak proxy its class
    found = [o for o in gc.get_objects() if issubclass(type(o), cls)]
    return found[0] if len(found) == 1 else None


def program_scopes(ctx):
    """``op_scopes`` of the program the run traced, read once a run and
    kept in ``ctx["op_scopes"]``; None where it cannot be read."""
    if "op_scopes" not in ctx:
        from bench.common import CompileClock, now
        t0, rec, scopes = now(), {}, None
        try:
            cell = live_cell(ctx)
            if cell is None:
                rec["error"] = "no single live cell"
            else:
                with CompileClock() as cc:
                    scopes = op_scopes(program_text(cell))
                rec.update(ops=len(scopes), compile=cc.as_dict())
        except Exception as e:          # a metric leaves itself out
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["seconds"] = now() - t0
        print("program_text " + json.dumps(rec, default=float), flush=True)
        ctx["op_scopes"] = scopes
    return ctx["op_scopes"]


def stage_share(ctx, tags):
    """Share (%) of the traced busy time in the operations under ``tags``,
    or None (an untraced run, a program without the scopes)."""
    if ctx.get("trace") is None:
        return None
    return scope_share(ctx["trace"], program_scopes(ctx), tags)
