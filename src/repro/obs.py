"""Names of the spans and layer scopes the secure job path writes into a trace.

Host spans are `jax.profiler.TraceAnnotation`s: each lands in a profiler
trace on the host plane, on the same clock as the device planes, with its
`job=<id>` (and `chunk=<i>`) arguments as event stats. Without an open
profiler session a span costs one object and two calls.

Device layers are `jax.named_scope`s: they name the ops traced inside them
in the compiled program's `op_name` metadata and change nothing else.
`op_layers` maps each instruction of a compiled program's text to the
innermost layer scope it was traced under.

    span               thread            covers
    repro.submit       caller            `submit_*`, entry to enqueue
    repro.prepare      scheduler         `make_gen` (padding, initial state,
                                         input placement) and the driver's
                                         placement of inputs and state
    repro.dispatch     scheduler         one chunk's runner call
    repro.readback     scheduler         the chunk's `n_exec`, halt flag,
                                         aux and overflow counts to the host
    repro.finalize     scheduler         the job's result to the host
"""

from __future__ import annotations

import re

from jax.profiler import TraceAnnotation

SUBMIT = "repro.submit"
PREPARE = "repro.prepare"
DISPATCH = "repro.dispatch"
READBACK = "repro.readback"
FINALIZE = "repro.finalize"
SPANS = (SUBMIT, PREPARE, DISPATCH, READBACK, FINALIZE)

MAP = "map"
REDUCE = "reduce"
HALT = "halt"
BUCKET_PACK = "bucket_pack"
KEYSTREAM = "keystream"
EXCHANGE = "exchange"
LAYERS = (MAP, REDUCE, HALT, BUCKET_PACK, KEYSTREAM, EXCHANGE)

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%\S+ = .*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r", metadata=\{[^}]*\}")
# the debug tables `as_text()` prints before the computations
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def span(name: str, job=None, chunk=None) -> TraceAnnotation:
    """A host span named `name`, with the job id and chunk index it is for."""
    args = {k: v for k, v in (("job", job), ("chunk", chunk)) if v is not None}
    return TraceAnnotation(name, **args)


def layer_of(op_name: str) -> str | None:
    """The innermost layer scope in an `op_name` path, or None.

    The path's last component names the primitive (`.../reduce/sort`), so it
    is never read as a scope: `lax.reduce` is a primitive named `reduce`.
    """
    for part in reversed(op_name.split("/")[:-1]):
        if part in LAYERS:
            return part
    return None


def op_layers(hlo_text: str) -> dict:
    """Map each instruction of an HLO module's text, up to `, metadata=`,
    to the innermost layer scope in its `op_name` (None where it has none)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        text, _, meta = m.group(1).partition(", metadata=")
        name = _OP_NAME.search(meta)
        out[text] = layer_of(name.group(1)) if name else None
    return out


def strip_metadata(hlo_text: str) -> str:
    """An HLO module's text without its metadata: the `metadata={...}` of
    every instruction and the source tables printed before the computations."""
    out, skipping = [], False
    for line in hlo_text.splitlines():
        if line.strip() in _DEBUG_TABLES:
            skipping = True
            continue
        if skipping:
            if line.strip():
                continue
            skipping = False
        out.append(_METADATA.sub("", line))
    return "\n".join(out)
