"""Device time by library stage, from the compiled program's ``op_name``s.

The library opens a stage (``jax.named_scope("custen.<stage>")``,
``repro.obs.stage``) around each phase of a step: ``adi.x``, ``adi.y``,
``adi.z``, ``ch.rhs``, ``ch.update``, ``ch.bootstrap``, ``stencil``.  The
scope reaches the ``op_name`` metadata of every HLO instruction traced
inside it, Pallas kernels and XLA fusions alike (a fusion keeps its
root's).  An instruction's stage is the outermost ``custen.`` name in its
``op_name``; one with none is XLA's own (a copy it inserted) or the loop
around the steps: *unscoped*.  Each device op is charged to one stage, so
the stages' seconds plus the unscoped seconds are the busy time.

Everything here only adds to :mod:`yardstick.trace` and reads a program
that may lack the scopes (a library from before them): its ops are then
all unscoped, and nothing raises.  :func:`without_debug_info` compares
two builds' programs apart from their names and source lines.
"""

from __future__ import annotations

import base64
import hashlib
import re

from yardstick import trace as _trace

PREFIX = "custen."
UNSCOPED = "unscoped"

_STAGE = re.compile(r"(?:^|/)custen\.([\w.]+?)(?=/|$)")
_METADATA = re.compile(r",?\s*metadata=\{[^{}]*\}")
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_TABLES = re.compile(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
                     r"(?:[^\n]+\n)*\n?", re.M)


def op_names(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` of every instruction of a compiled
    program's HLO text that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m, op = _trace._INSTR.match(line), _trace._OP_NAME.search(line)
        if m and op:
            out[m.group(1)] = op.group(1)
    return out


def stage_of(op_name: str | None) -> str | None:
    """The outermost ``custen.`` stage of an ``op_name``, without its
    prefix, or None."""
    m = _STAGE.search(op_name or "")
    return m.group(1) if m else None


def stage_seconds(red: _trace.Reduction, names: dict) -> dict:
    """Seconds of device time inside the window by stage, ``unscoped``
    for ops with none; the values add up to ``red.busy_s`` when no two
    ops of a chip overlap."""
    out = {}
    for instr, seconds in red.op_s.items():
        key = stage_of(names.get(instr)) or UNSCOPED
        out[key] = out.get(key, 0.0) + seconds
    return out


def kernel_ms(ctx, stage: str) -> float | None:
    """Milliseconds a step of the Pallas kernels (``ctx.kernels``) whose
    outermost stage is ``stage``, or None when none is (a program from
    before the scopes, or a cell without that stage)."""
    seconds = sum(ctx.red.op_s.get(instr, 0.0) for instr, op in ctx.kernels.items()
                  if stage_of(op) == stage)
    return seconds * 1e3 / ctx.steps if seconds > 0 else None


def _kernel_body(encoded: str) -> str:
    """A Pallas kernel's Mosaic module without its source locations (the
    file, line and scope of each traced line of Python)."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(encoded))
        asm = module.operation.get_asm(enable_debug_info=False)
    return hashlib.sha256(asm.encode()).hexdigest()


def without_debug_info(hlo_text: str) -> str:
    """A compiled program's HLO text without what names its source: the
    ``metadata={...}`` of each instruction, the tables of files, functions
    and frames that metadata points into, and the locations inside each
    Pallas kernel's body.  Two programs that differ only in scopes, paths
    or line numbers give the same text."""
    text = _TABLES.sub("", hlo_text)
    text = _METADATA.sub("", text)
    return _BODY.sub(lambda m: f'"body":"{_kernel_body(m.group(1))}"', text)
