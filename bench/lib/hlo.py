"""HLO instruction text, as the profiler names each device op
(``%w8a8_matmul.135 = f32[128,1024]{...} custom-call(s8[128,2048]{...}
%x, s8[2048,1024]{...} %w, ...), custom_call_target=...``): the op's name,
kind, result and operand shapes.  A Pallas launch is named after its jitted
wrapper (``w8a8_matmul``, ``paged_kv_decode_attention``,
``paged_prefix_chunk_attention``, ``jit_fused_quant_``)."""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

from . import flops

_HEAD = re.compile(r"^%?(?P<name>[\w.\-]+) = (?P<out>.*?) (?P<kind>[a-z][\w\-]*)"
                   r"\((?P<args>.*)$")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
CONTAINERS = ("while", "conditional", "call")


class Op:
    """One parsed instruction."""

    def __init__(self, name: str, kind: str, out: list, args: list):
        self.name, self.kind, self.out, self.args = name, kind, out, args

    @property
    def base(self) -> str:
        """The name without its numeric suffix: ``w8a8_matmul``."""
        return self.name.rsplit(".", 1)[0]

    def label(self) -> str:
        shape = ",".join(f"{dt}[{','.join(map(str, d))}]"
                         for dt, d in self.out[:1])
        return f"{self.name} {self.kind} {shape}".strip()


def _shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def parse(text: str) -> Optional[Op]:
    """Parse an instruction's text; None where it is not one (a plain op
    name)."""
    m = _HEAD.match(text)
    if not m:
        return None
    args = m["args"].split("), ", 1)[0]
    return Op(m["name"], m["kind"], _shapes(m["out"]), _shapes(args))


def w8a8_cost(op: Op) -> Tuple[int, int]:
    """(operations, bytes) of a W8A8 launch from its padded shapes: the
    int8 (M, K) activation and (K, N) weight are its first two operands."""
    (_, (m, k)), (_, (k2, n)) = op.args[0], op.args[1]
    if k != k2:
        raise ValueError(f"not a W8A8 GEMM: {op.args}")
    return flops.w8a8_call(m, k, n)


COSTS = {"w8a8_matmul": w8a8_cost}
