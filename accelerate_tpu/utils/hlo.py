"""Tiny HLO/StableHLO text introspection helpers.

Used by the comm-hook wire-bytes proof (tests) and the telemetry
recorder's per-compile collective accounting: both need "how many bytes do
the collective ops in this module move, by dtype" — one parser so the
regexes can't drift apart. Matched ops: ``all-reduce``, ``all-gather``,
``reduce-scatter`` (the FSDP pair — a sharded step's traffic is mostly
gather/scatter, not all-reduce). Bytes are the ops' RESULT-shape bytes: an
ICI/DCN traffic proxy, not an exact wire model (a ring all-reduce moves
~2x the buffer, an all-gather's result is the already-concatenated
buffer). No reference analog (torch exposes comm bytes via NCCL debug
env; XLA exposes the program text).
"""

from __future__ import annotations

import re

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "i64": 8, "i32": 4, "i16": 2, "i8": 1,
}

#: ``"stablehlo.all_reduce"(%x) ... : (tensor<32x32xbf16>) -> ...`` —
#: pre-optimization module: the wire dtype as TRACED (what TPU executes;
#: XLA:CPU's backend pass may later promote bf16 collectives to f32)
_STABLEHLO_COLLECTIVE = re.compile(
    r"stablehlo\.(all_reduce|all_gather|reduce_scatter)"
    r".*?\(tensor<([0-9x]*)x?(\w+)>\)\s*->",
    re.DOTALL,
)

#: ``%ar = (f32[], f32[32,32]) all-reduce(...)`` — compiled HLO form,
#: including tuple-shaped combined collectives
#: the optional ``-start`` suffix matches the async forms TPU's compiler
#: emits (``all-reduce-start``/``all-gather-start``/...); without it the
#: parser reads 0 bytes on exactly the platform that matters
_HLO_COLLECTIVE = re.compile(
    r"=\s*\(?((?:\w+\[[0-9,]*\][^)=]*?,?\s*)+)\)?\s*"
    r"(all-reduce|all-gather|reduce-scatter)(-start)?\("
)
_HLO_SHAPE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _numel(dims: str, sep: str) -> int:
    n = 1
    for d in dims.split(sep):
        if d:
            n *= int(d)
    return n


def stablehlo_collective_bytes(text: str) -> dict[str, dict[str, int]]:
    """{op: {dtype: operand bytes}} over every StableHLO collective op."""
    out: dict[str, dict[str, int]] = {}
    for m in _STABLEHLO_COLLECTIVE.finditer(text):
        op, dims, dtype = m.group(1), m.group(2), m.group(3)
        per_op = out.setdefault(op.replace("_", "-"), {})
        per_op[dtype] = per_op.get(dtype, 0) + _numel(dims, "x") * _DTYPE_BYTES.get(dtype, 4)
    return out


def hlo_collective_bytes(text: str) -> dict[str, dict[str, int]]:
    """{op: {dtype: result bytes}} over every compiled-HLO collective op.
    Sync tuple forms are combined collectives (every element is a result);
    async ``-start`` forms return ``(operand-alias, result)`` — only the
    result element counts, or TPU modules would double-report."""
    out: dict[str, dict[str, int]] = {}
    for m in _HLO_COLLECTIVE.finditer(text):
        per_op = out.setdefault(m.group(2), {})
        shapes = list(_HLO_SHAPE.finditer(m.group(1)))
        if m.group(3) and len(shapes) > 1:  # -start: last element is the result
            shapes = shapes[-1:]
        for t in shapes:
            dtype, dims = t.group(1), t.group(2)
            per_op[dtype] = per_op.get(dtype, 0) + _numel(dims, ",") * _DTYPE_BYTES.get(dtype, 4)
    return out


def total_collective_bytes(text: str) -> int:
    """Sum of all collective-op bytes in a compiled-HLO module (the single
    number the telemetry compile record carries)."""
    return sum(
        b for per_op in hlo_collective_bytes(text).values() for b in per_op.values()
    )


def stablehlo_allreduce_bytes(text: str) -> dict[str, int]:
    """{dtype: operand bytes} over every ``stablehlo.all_reduce`` op."""
    return stablehlo_collective_bytes(text).get("all-reduce", {})


def hlo_allreduce_bytes(text: str) -> dict[str, int]:
    """{dtype: result bytes} over every compiled-HLO ``all-reduce`` op."""
    return hlo_collective_bytes(text).get("all-reduce", {})


#: ``%fusion.12 = bf16[64,4096]{1,0:T(8,128)(2,1)} fusion(...), calls=%f.3,
#: metadata={op_name="jit(decode)/.../mlp/dot_general" ...}`` — one
#: instruction of a compiled module: name, first result array, the rest
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.-]+) = \(*([a-z]\w*\[[0-9,]*\])?(.*)$")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(")
_HLO_OP_NAME = re.compile(r'\bop_name="([^"]+)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.-]+)")


def op_scopes(text: str) -> dict[str, tuple[str, str]]:
    """``{instruction name: (result shape, scope stack)}`` of a compiled
    module's text: what a device trace needs to put an executed operation
    under the ``jax.named_scope`` it came from — the TPU profiler names an
    event by its instruction, not by its ``op_name``. The result shape
    (``bf16[64,4096]``, the first array of a tuple) tells apart the
    instructions of two programs that share a name. A fusion the compiler
    left without an ``op_name`` takes the deepest stack among the
    instructions it fused (a callee is printed before its caller);
    instructions that are the compiler's own throughout (layout copies)
    are left out."""
    table: dict[str, tuple[str, str]] = {}
    deepest: dict[str, str] = {}  # computation -> the deepest stack inside it
    computation = ""
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name, shape, rest = m.groups()
        own = _HLO_OP_NAME.search(rest)
        if own:
            stack = own.group(1)
        else:
            callee = _HLO_CALLS.search(rest)
            stack = deepest.get(callee.group(1), "") if callee else ""
        if stack:
            table[name] = (shape or "", stack)
            if stack.count("/") >= deepest.get(computation, "").count("/"):
                deepest[computation] = stack
    return table


#: opcodes through which a buffer passes without a byte of it moving: the
#: entry and loop parameters, tuple plumbing, and a reinterpretation
_HLO_PLUMBING = frozenset({
    "parameter", "get-tuple-element", "tuple", "while", "conditional", "call",
    "bitcast", "optimization-barrier",
})
#: opcodes that write part of their first operand and hand the same buffer
#: on (buffer assignment updates a loop-carried or donated operand in place)
_HLO_IN_PLACE = frozenset({"scatter", "dynamic-update-slice"})
_HLO_RESULT = re.compile(r"^\s+(ROOT )?%?([\w.-]+) = (.*?)\s([a-z][\w-]*)\(")
_HLO_ALIASED_PARAM = re.compile(r"\}:\s*\((\d+),")
_HLO_PARAM_NUMBER = re.compile(r"\bparameter\((\d+)\)")


def buffers_moved(text: str, numels) -> dict:
    """Does a compiled module leave its big buffers where they are?
    ``numels`` are element counts to watch (the serving engine's KV pool,
    one layer's slab of it). Returns ``{"moved": [(instruction, opcode,
    result shape), ...], "unaliased": [entry parameter numbers]}``:

    * ``moved`` — every instruction that PRODUCES an array of a watched
      size other than by passing it on (parameters, tuple plumbing,
      bitcasts) or by updating it in place (``scatter`` /
      ``dynamic-update-slice``, bare or as the root of a fusion): a
      ``copy``, a ``dynamic-slice`` of a slab, a ``reshape`` or
      ``transpose`` that was materialised, an async ``copy-start``;
    * ``unaliased`` — entry parameters of a watched size that the module
      header's ``input_output_alias`` does not map onto an output, i.e.
      donated buffers the program could not reuse.

    Both empty is the compiled-text form of "the pool never moves"."""
    watched = {int(n) for n in numels}
    roots: dict[str, str] = {}  # computation -> opcode of its ROOT
    moved: list[tuple[str, str, str]] = []
    fusions: list[tuple[str, str, str]] = []  # (name, shape, callee)
    entry_params: list[int] = []
    computation, in_entry = "", False
    lines = text.splitlines()
    header = next((l for l in lines if l.startswith("HloModule")), "")
    alias = header.partition("input_output_alias={")[2].partition("entry_computation_layout")[0]
    aliased = {int(n) for n in _HLO_ALIASED_PARAM.findall(alias)}
    for line in lines:
        head = _HLO_COMPUTATION.match(line)
        if head:
            computation, in_entry = head.group(1), line.startswith("ENTRY")
            continue
        m = _HLO_RESULT.match(line)
        if not m:
            continue
        is_root, name, result, opcode = m.groups()
        if is_root:
            roots[computation] = opcode
        hit = [
            f"{t.group(1)}[{t.group(2)}]" for t in _HLO_SHAPE.finditer(result)
            if _numel(t.group(2), ",") in watched
        ]
        if not hit or opcode in _HLO_PLUMBING:
            if hit and opcode == "parameter" and in_entry:
                entry_params.append(int(_HLO_PARAM_NUMBER.search(line).group(1)))
            continue
        if opcode in _HLO_IN_PLACE:
            continue
        callee = _HLO_CALLS.search(line) if opcode == "fusion" else None
        if callee:
            fusions.append((name, hit[0], callee.group(1)))
        else:
            moved.append((name, opcode, hit[0]))
    for name, shape, callee in fusions:  # a callee is printed before its caller
        if roots.get(callee) not in _HLO_IN_PLACE:
            moved.append((name, f"fusion:{roots.get(callee)}", shape))
    return {
        "moved": moved,
        "unaliased": sorted(p for p in entry_params if p not in aliased),
    }


_HLO_CALLEES = re.compile(r"\b(?:calls|to_apply|body|condition)=%?([\w.-]+)")
_HLO_WHILE_BODY = re.compile(r"\bwhile\(.*\bbody=%?([\w.-]+)")


def loop_instructions(text: str) -> dict[str, list[tuple[str, str, int, str]]]:
    """``{while body: [(instruction, opcode, result elements, op_name)]}``
    of a compiled module's text: every instruction that runs inside each
    loop, those of the computations the body calls (fusions, reducers,
    nested loops) among them. ``result elements`` counts the largest array
    of the result. What a test reads to say "no collective over an operand
    of that size sits inside the loop" or "a round costs three products"."""
    computations: dict[str, list[str]] = {}
    current: list[str] | None = None
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            current = computations.setdefault(head.group(1), [])
        elif current is not None and line.startswith(" "):
            current.append(line)

    def reach(name: str, seen: set) -> set:
        if name in computations and name not in seen:
            seen.add(name)
            for line in computations[name]:
                for callee in _HLO_CALLEES.findall(line):
                    reach(callee, seen)
        return seen

    out: dict[str, list[tuple[str, str, int, str]]] = {}
    for body in sorted(set(_HLO_WHILE_BODY.findall(text))):
        rows = out.setdefault(body, [])
        for name in sorted(reach(body, set())):
            for line in computations[name]:
                m = _HLO_RESULT.match(line)
                if m:
                    numel = max((_numel(t.group(2), ",") for t in _HLO_SHAPE.finditer(m.group(3))),
                                default=0)
                    scope = _HLO_OP_NAME.search(line)
                    rows.append((m.group(2), m.group(4), numel, scope.group(1) if scope else ""))
    return out
