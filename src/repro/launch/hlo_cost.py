"""Trip-count-aware HLO cost model.

``compiled.cost_analysis()`` counts every computation ONCE — a fit's
sweep loop and its chunk scan are under-counted by their trip counts,
which makes the naive numbers useless for a roofline.  The analyzer
re-derives per-chip FLOPs, HBM bytes and collective bytes from the
optimized HLO text with loop multiplication:

  · computations are parsed into op lists (name → result type, opcode,
    operands, attributes);
  · ``while`` trip counts come from the loop-condition computation (the
    compare-against-constant emitted by lax.scan/fori_loop; dynamic
    ``while_loop`` bounds fall back to 1 and are flagged);
  · FLOPs: dots contribute 2·numel(result)·contraction_size (operand shapes
    resolved within the computation); called computations (fusions, loop
    bodies, reducers) are charged recursively × multiplier;
  · HBM bytes: for surface ops that touch memory (fusion, dot, copy,
    gather/scatter, dynamic-(update-)slice, reduce, sort, collectives,
    parameter-free broadcast excluded), operand sizes + result size — i.e.
    the traffic of the fused kernel, not its internals;
  · collective bytes: result sizes of collective ops × multiplier, split by
    family.

This is a *model* of the per-device program, exact on loop structure,
approximate on fusion internals.

The parser and the analyzer itself now live in
:mod:`repro.analysis.hlo_ir` (promoted in ISSUE 8 so the static-analysis
rules and this cost model share ONE parser); this module keeps the
historical import surface.
"""
from __future__ import annotations

from repro.analysis.hlo_ir import (  # noqa: F401
    COLLECTIVES,
    DTYPE_BYTES,
    Cost,
    Op,
    analyze,
    parse_computations,
    parse_op_line,
    trip_count,
    type_numel_bytes,
)

# historical (pre-promotion) names, kept for downstream callers/tests
_DTYPE_BYTES = DTYPE_BYTES
_parse_op_line = parse_op_line
_trip_count = trip_count
_type_numel_bytes = type_numel_bytes
