"""The paper's first-order bandwidth model (eqs 1-7) over `ConvWorkload`.

This is the single implementation of the analytical model (`conv_terms`).
Semantics (and numbers) are identical to the seed implementation:

  constraint (eq 1):  K^2 * m * n <= P
  input BW   (eq 2):  B_i = Wi*Hi*M * (N/n)          (re-read per output block)
  output BW  (eq 3):  B_o = Wo*Ho*N * (2*M/m - 1)    (write + read-before-update)
  optimum    (eq 7):  m* = sqrt(2*Wo*Ho*P / (Wi*Hi*K^2)), snapped to a factor of M

with the active-memory-controller variant of Section III (B_o = Wo*Ho*N * M/m)
and per-group handling of grouped/depthwise convolutions (M/m and N/n count
within one group). The accumulator SRAM meter counts whole passes in either
iteration convention: every input word is read once per arrival, and the
accumulator is written every pass and read on every pass but the first
(internally when active, over the bus when passive):

  SRAM reads:   B_i + (ceil(M/m) - 1) * Wo*Ho*N
  SRAM writes:  ceil(M/m) * Wo*Ho*N
"""

from __future__ import annotations

import functools
import math
import types
from typing import NamedTuple

import numpy as np

from repro.plan.schedule import Controller, Schedule, Strategy
from repro.plan.workload import ConvWorkload


def _factors(x: int) -> list[int]:
    fs = [d for d in range(1, int(math.isqrt(x)) + 1) if x % d == 0]
    return sorted(set(fs + [x // d for d in fs]))


def _snap_to_factor(value: float, total: int, cap: int) -> int:
    """Snap a real-valued block size to the nearest integer factor of `total`
    that does not exceed `cap` (the paper's adaptation of eq 7)."""
    cands = [f for f in _factors(total) if f <= cap]
    return min(cands, key=lambda f: (abs(f - value), f)) if cands else 1


class ConvTerms(NamedTuple):
    """`conv_terms`' result: iteration counts (int64 under ceil counts,
    float64 under the paper's M/m) and float64 words and bytes, 0-d for one
    schedule, one entry per candidate for arrays."""

    in_iters: np.ndarray     # accumulation passes, M/m
    out_iters: np.ndarray    # output blocks, N/n: reads of each input word
    b_i: np.ndarray          # eq (2)
    b_o: np.ndarray          # eq (3), or its active variant
    total: np.ndarray
    sram_reads: np.ndarray
    sram_writes: np.ndarray
    bytes: np.ndarray        # interconnect words at the word width
    sram_bytes: np.ndarray   # SRAM accesses at the word width

    @property
    def input_words(self) -> np.ndarray:
        return self.b_i

    @property
    def output_words(self) -> np.ndarray:
        return self.b_o

    @property
    def read_iters(self) -> np.ndarray:
        return self.out_iters

    @property
    def weight_reads(self) -> np.ndarray:
        """The paper's model charges no weight traffic."""
        return np.zeros_like(self.b_i)


def conv_terms(wl: ConvWorkload, m, n, controller: Controller,
               exact_iters: bool = True) -> ConvTerms:
    """Eqs (2)/(3) and the SRAM meter (module docstring) for one layer under
    an (m, n) partition: scalars for one schedule or candidate arrays.

    ``exact_iters=True`` uses ceil(M/m) iteration counts (valid for any
    integer m, n) in exact int64 arithmetic with one final float64
    conversion; False uses the paper's real-valued M/m. ``wl`` may also hold
    per-candidate arrays in the fields read here (``cin``, ``cout``,
    ``groups``, ``in_acts``, ``out_acts``, ``word_bytes``), which is how
    `conv_exact_search_batch` evaluates a whole network at once."""
    controller = Controller.coerce(controller)
    mg, ng = wl.cin // wl.groups, wl.cout // wl.groups
    m_eff = np.minimum(np.asarray(m, np.int64), mg)
    n_eff = np.minimum(np.asarray(n, np.int64), ng)
    passes = -(-mg // m_eff)
    if exact_iters:
        in_iters, out_iters = passes, -(-ng // n_eff)
    else:
        in_iters, out_iters = mg / m_eff, ng / n_eff
    b_i = wl.in_acts * out_iters
    writes = wl.out_acts * in_iters
    if controller is Controller.ACTIVE:
        b_o = writes                      # controller adds locally; write-only
    else:
        b_o = 2 * writes - wl.out_acts    # + read-before-update
    sram_reads = b_i + (passes - 1) * wl.out_acts
    sram_writes = passes * wl.out_acts
    f64 = functools.partial(np.asarray, dtype=np.float64)
    return ConvTerms(
        in_iters=in_iters, out_iters=out_iters, b_i=f64(b_i), b_o=f64(b_o),
        total=f64(b_i + b_o), sram_reads=f64(sram_reads),
        sram_writes=f64(sram_writes),
        bytes=f64((b_i + b_o) * wl.word_bytes),
        sram_bytes=f64((sram_reads + sram_writes) * wl.word_bytes))


def optimal_m_realvalued(wl: ConvWorkload, p_macs: int,
                         controller: Controller = Controller.PASSIVE) -> float:
    """eq (7), and its active-controller refinement (beyond-paper): with free
    read-back the objective loses the factor 2 -> m* = sqrt(Wo*Ho*P/(Wi*Hi*K^2))."""
    factor = 2.0 if controller is Controller.PASSIVE else 1.0
    return math.sqrt(factor * wl.wo * wl.ho * p_macs
                     / (wl.wi * wl.hi * wl.k * wl.k))


def conv_exact_candidates(wl: ConvWorkload, p_macs: int
                          ) -> tuple[np.ndarray, np.ndarray]:
    """The seed exact search's candidate set as arrays, in its iteration
    order: every integer m in [1, min(M/g, P/K^2)] with the greedy
    bandwidth-optimal n = min(N/g, max(1, (P/K^2) / m)) of eq (5)."""
    g = wl.groups
    mg, ng = wl.cin // g, wl.cout // g
    budget = max(1, p_macs // (wl.k * wl.k))
    m = np.arange(1, min(mg, budget) + 1, dtype=np.int64)
    n = np.minimum(ng, np.maximum(1, budget // m))
    return m, n


def closed_form_mn(wl: ConvWorkload, p_macs: int, strategy: Strategy
                   ) -> tuple[int, int]:
    """The paper's four closed-form partition rules (Section II): (m, n) for
    one layer under ``max_input`` / ``max_output`` / ``equal`` / ``paper_opt``
    (eq 7 snapped to a factor of M). Exactly the seed formulas."""
    g = wl.groups
    mg, ng = wl.cin // g, wl.cout // g
    budget = max(1, p_macs // (wl.k * wl.k))
    if strategy is Strategy.MAX_INPUT:
        m = min(mg, budget)
        n = min(ng, max(1, budget // m))
    elif strategy is Strategy.MAX_OUTPUT:
        n = min(ng, budget)
        m = min(mg, max(1, budget // n))
    elif strategy is Strategy.EQUAL:
        side = max(1, int(math.isqrt(budget)))
        m = min(mg, side)
        n = min(ng, max(1, budget // m))
    elif strategy is Strategy.PAPER_OPT:
        # eq (7): m* = sqrt(2 * Wo*Ho * P / (Wi*Hi * K^2))
        m_star = math.sqrt(2.0 * wl.wo * wl.ho * p_macs
                           / (wl.wi * wl.hi * wl.k * wl.k))
        m = _snap_to_factor(m_star, mg, cap=min(mg, budget))
        n = min(ng, max(1, budget // m))  # eq (5): n = P / (K^2 m)
    else:
        raise ValueError(f"strategy {strategy} has no conv closed form")
    return m, n


def conv_exact_search_batch(workloads, p_macs: int, controller: Controller
                            ) -> list[tuple[int, int]]:
    """Vectorized exact search over a whole network in one shot: concatenate
    every layer's candidate set, evaluate `conv_terms` on the flat arrays, and
    take one segmented argmin. Bit-for-bit the seed's per-candidate loop's
    choices (first minimum wins, as strict ``<`` does in the loop)."""
    workloads = list(workloads)
    if not workloads:
        return []
    cand_m, cand_n, lengths = [], [], []
    for wl in workloads:
        m, n = conv_exact_candidates(wl, p_macs)
        cand_m.append(m)
        cand_n.append(n)
        lengths.append(len(m))
    m = np.concatenate(cand_m)
    n = np.concatenate(cand_n)
    seg = np.repeat(np.arange(len(workloads)), lengths)

    def per_wl(fn):
        return np.repeat(np.fromiter((fn(w) for w in workloads), np.int64,
                                     len(workloads)), lengths)

    network = types.SimpleNamespace(
        cin=per_wl(lambda w: w.cin), cout=per_wl(lambda w: w.cout),
        groups=per_wl(lambda w: w.groups),
        in_acts=per_wl(lambda w: w.in_acts),
        out_acts=per_wl(lambda w: w.out_acts),
        word_bytes=per_wl(lambda w: w.word_bytes))
    cost = conv_terms(network, m, n, controller, exact_iters=True).total

    # Segmented first-minimum argmin: stable sort by (segment, cost, position)
    # then pick each segment's first row.
    order = np.lexsort((np.arange(cost.size), cost, seg))
    starts = np.searchsorted(seg[order], np.arange(len(workloads)))
    best = order[starts]
    return [(int(m[i]), int(n[i])) for i in best]


def plan_conv(wl: ConvWorkload, p_macs: int, strategy: Strategy,
              controller: Controller) -> Schedule:
    """Choose (m, n) for a layer given P MACs under one of the paper's four
    strategies, or the beyond-paper exact integer search (`EXACT_OPT`).

    For `EXACT_OPT` the objective honours the controller (active controllers
    shift the optimum: the factor 2 in eq 7 disappears when read-back is free).
    The four paper strategies are controller-agnostic, as in the paper.

    Every strategy is a `repro.plan.dse` preset of (space, constraints,
    objective); this function is the conv-flavoured entry point to that
    machinery (lazy import: ``dse`` builds on this module's evaluators).
    """
    from repro.plan import dse
    return dse.plan_with_strategy(wl, p_macs, strategy, controller)


def min_conv_bandwidth(workloads) -> float:
    """Table III: unlimited MACs — each layer reads its input once and writes
    its output once (eq 4 with m=M, n=N)."""
    return float(sum(w.in_acts + w.out_acts for w in workloads))
