"""Which `jax.named_scope`s an operation of a trace ran under.

The profile's `XLA Ops` events carry an instruction's name and text, a start and a
duration, and nothing of where in the program it came from (their stats are
`device_offset_ps`, `device_duration_ps`; PR 31 looked). The compiled program's text has
it: every instruction's `op_name` metadata is its path of scopes
(`jit(step)/jvp()/while/body/closed_call/mlp/moe_experts/ragged_dot`), and a fusion's
body has its parts'. The two are joined by the instruction's name, which the trace and
the text of the SAME compiled program share.

    scopes_by_instruction(text)   {instruction: {scope, ...}}: the plain names on the
                                  paths of the instruction and, for a fusion or a call,
                                  of its body's instructions (the last name of a path is
                                  the primitive's and is left out; `jit(..)`, `jvp(..)`
                                  and einsum strings are not names)
    op_scopes(op_seconds, text)   {operation as the trace names it: sorted scopes}
    seconds(op_seconds, scopes)   {scope: seconds of the operations under it}; an
                                  operation under several scopes counts under each

Read by `readers/trace_scope_share.py`; `drivers/train_family.py` makes the join.
"""
import re

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAME = re.compile(r"^[A-Za-z_]\w*$")


def _names(lines) -> set:
    found = set()
    for line in lines:
        for path in _OP_NAME.findall(line):
            found.update(part for part in path.split("/")[:-1] if _NAME.match(part))
    return found


def scopes_by_instruction(text: str) -> dict:
    computations, current = {}, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head and line.rstrip().endswith("{"):
            current = computations.setdefault(head.group(1), [])
        elif current is not None:
            current.append(line)
    own = {name: _names(body) for name, body in computations.items()}
    out = {}
    for body in computations.values():
        for line in body:
            inst = _INSTRUCTION.match(line)
            if inst:
                scopes = _names([line])
                for called in _CALLS.findall(line):
                    scopes |= own.get(called, set())
                out[inst.group(1)] = scopes
    return out


def op_scopes(op_seconds: dict, text: str) -> dict:
    by_instruction = scopes_by_instruction(text)
    return {op: sorted(by_instruction.get(op.partition(" = ")[0].strip().lstrip("%"), ()))
            for op in op_seconds}


def seconds(op_seconds: dict, scopes: dict) -> dict:
    out = {}
    for op, s in op_seconds.items():
        for scope in scopes.get(op, ()):
            out[scope] = out.get(scope, 0.0) + s
    return out
