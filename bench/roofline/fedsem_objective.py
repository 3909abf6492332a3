"""Operations and bytes of one call of the FedSem objective kernel, from its shapes.

The kernel scores G candidate allocations of each of B scenarios of N
devices (eq. 13): per device and candidate the upload and compute delays,
the three energies and their masked sum, and the delay's masked maximum;
per candidate the accuracy term and the weighted sum. The count is of the
arithmetic eq. 13 needs at those shapes, whatever implements it: one
operation per add, multiply, divide, compare, select, max or
transcendental. Bytes are the float32 operands read once and the output
written once.

The shapes are the problem's logical ones (`Shapes`): the flush's slots, the
deployment's real devices, and the candidates a call site scores, never the
padded operands of a compiled call.
"""
from __future__ import annotations

from typing import NamedTuple

#: per device and candidate: max(r, eps), D/r, max(f, eps), eta*c*d/f (2),
#: p*tau, f*f and its scaling (2), p*rho*C/r (3), the energy sum (2) and its
#: mask (1), tau + t_c and its mask (2), the two reductions over devices (2)
OPS_PER_DEVICE = 19
#: per candidate: max(rho, 1e-9), log, multiply, exp, scale by a, the three
#: weighted terms (3) and their sum (2), the device count's multiply (1)
OPS_PER_CANDIDATE = 11
#: the feasibility mask, when asked: rho*C/r (2), two compares with their
#: masks and reductions (6) per device and candidate; one select per candidate
OPS_FEASIBLE_PER_DEVICE = 8
OPS_FEASIBLE_PER_CANDIDATE = 1
#: float32 operands: f, p, r per device and candidate; rho and the output per
#: candidate; c, d, D, C, t_sc_max, f_max and the mask per device; the three
#: weights and the accuracy fit's two coefficients per scenario
WORD = 4
#: starting points of the allocator's multi-start solve per inner solver
#: (equal share, low power, full payload: Alg. A2's starts)
STARTS = 3


def counts(B: int, N: int, G: int, check_feasible: bool = False) -> tuple[int, int]:
    """(operations, bytes) of one call over B scenarios, N devices, G candidates."""
    per_dev = OPS_PER_DEVICE + (OPS_FEASIBLE_PER_DEVICE if check_feasible else 0)
    per_cand = OPS_PER_CANDIDATE + (OPS_FEASIBLE_PER_CANDIDATE if check_feasible else 0)
    ops = B * G * (N * per_dev + per_cand)
    words = B * (3 * N * G + 2 * G + 7 * N + 5)
    return ops, words * WORD


class Shapes(NamedTuple):
    """Logical shapes of a cell's kernel calls.

    ``B`` scenarios per call (the flush's slots), ``N`` real devices, and per
    program the candidates its last call scores: the solve's multi-start
    selection, the refine's choice between the cold answer and the cached
    starts. Every other call scores one candidate: the solver's per-iteration
    objective, the flush's score of its answers.
    """

    B: int
    N: int
    select: dict

    @classmethod
    def of(cls, dep: dict, slots: int) -> Shapes:
        s = dep["serve"]
        inners = 2 if s["allocator"].get("inner", "sca") == "auto" else 1
        ws = s.get("warmstart")
        select = {"solve": STARTS * inners, "score": 1}
        if ws is not None:
            select["refine"] = 1 + int(ws.get("top_k", 1))
        return cls(slots, int(dep["N"]), select)

    def candidates(self, program: str, last: bool) -> int:
        if program not in self.select:
            raise RuntimeError(f"the objective kernel ran inside program {program!r}, "
                               f"which the reduction does not know: {sorted(self.select)}")
        return self.select[program] if last else 1
