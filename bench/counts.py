"""Operations and bytes of a round and of a kernel call, from shapes.

Counted from what the algorithm needs at the cell's shapes (clients N,
candidates n, trajectory capacity cap, dimension d, RFF features M, local
steps T, the objective's cost per query), never from a kernel's blocks or
padding, so the count stays the same whatever implements the work.  A
multiply-add is two operations.  Terms under ~1% of a round are left out:
the counts are a floor of the work, so a share of a peak computed from them
cannot pass 100% by over-counting.
"""

from __future__ import annotations


# ---------------------------------------------------------------------------
# objectives: operations of one query
# ---------------------------------------------------------------------------


def quadratic_query_flops(d: int) -> float:
    """f_i(x) = (sum_j wa_j r_j^2 + wb_j r_j + 1)/(10 d), r = 20 x - 10."""
    return 8.0 * d


def lm_query_flops(cfg: dict, tokens_per_seq: int, seqs: int) -> float:
    """What one query of the LM objective needs: the final RMSNorm with the
    queried gains and the tied output projection over the vocabulary
    (2 x d x vocab a token), then the log-sum-exp (~3 x vocab a token).

    The ZO input moves the final-norm gains only, so the decoder trunk below
    them does not depend on it: the trunk's output is the same for every
    query of a client, and the compiled round computes it apart from the
    queries.  It is left out of the count, which is then a floor."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    tok = tokens_per_seq * seqs
    return tok * (2.0 * d * v + 3.0 * v + 5.0 * d)


# ---------------------------------------------------------------------------
# kernels: one client-batched call
# ---------------------------------------------------------------------------


def gp_score_call(N: int, n: int, cap: int, d: int) -> dict:
    """Uncertainty scores of n candidates per client against its cap-point
    trajectory, given the masked inverse B and P = B o XX^T (both cap x cap):
    distances (2 n cap d + 2 n d + 2 cap d), the two cap x cap products per
    candidate (4 n cap^2) and the epilogue (~10 n cap).  Bytes: the
    candidates, trajectory, B and P read once, the scores written."""
    flops = N * (2.0 * n * cap * d + 2.0 * n * d + 2.0 * cap * d + 4.0 * n * cap * cap
                 + 10.0 * n * cap)
    nbytes = 4.0 * N * (n * d + cap * d + 2 * cap * cap + n)
    return {"flops": flops, "bytes": nbytes}


def gp_grad_call(N: int, cap: int, d: int) -> dict:
    """Posterior gradient mean at one point per client: distances
    (4 cap d + 2 d), the kernel weights (~4 cap) and (h o alpha) X (2 cap d).
    Bytes: point, trajectory and alpha read, the gradient written."""
    flops = N * (6.0 * cap * d + 2.0 * d + 4.0 * cap)
    nbytes = 4.0 * N * (cap * d + 2 * d + cap)
    return {"flops": flops, "bytes": nbytes}


def kernel_calls_per_round(algo: str, s: dict) -> dict:
    """Client-batched kernel calls of one round: one scoring call per local
    step and one at round end, one gradient mean per local step."""
    if algo != "fzoos":
        return {"gp_score": 0, "gp_grad": 0}
    return {"gp_score": s["local_steps"] + 1, "gp_grad": s["local_steps"]}


def kernel_call(name: str, s: dict) -> dict:
    N, cap, d = s["n_clients"], s["traj_capacity"], s["dim"]
    if name == "gp_score":
        return gp_score_call(N, s["active_candidates"], cap, d)
    if name == "gp_grad":
        return gp_grad_call(N, cap, d)
    raise KeyError(name)


# ---------------------------------------------------------------------------
# a whole round
# ---------------------------------------------------------------------------


def _scoring(n: int, cap: int, d: int) -> float:
    """Per client: the inverse from the factor (cap^3), P = B o XX^T
    (2 cap^2 d) and the kernel's per-candidate work."""
    return cap**3 + 2.0 * cap * cap * d + gp_score_call(1, n, cap, d)["flops"]


def fzoos_round_flops(s: dict, query_flops: float) -> float:
    """One FZooS round (Algo. 2) for all N clients."""
    N, d, cap, M, T = s["n_clients"], s["dim"], s["traj_capacity"], s["n_features"], \
        s["local_steps"]
    n, a, e = s["active_candidates"], s["active_per_iter"], s["active_round_end"]
    step = (
        (1 + a) * query_flops
        + 2.0 * (1 + a) * cap * d  # new Gram rows
        + 2.0 * (1 + a) * cap * cap  # bordered factor update
        + _scoring(n, cap, d)
        + 2.0 * cap * cap + gp_grad_call(1, cap, d)["flops"]  # alpha, grad mean
        + 8.0 * M * d  # two RFF gradients, grad phi(x)^T w
    )
    end = (
        e * query_flops
        + _scoring(n, cap, d)
        + 2.0 * e * cap * d + 2.0 * e * cap * cap
        + 2.0 * cap * M * d  # features of the trajectory
        + 2.0 * cap * cap * M + cap**3 / 3.0 + 2.0 * cap * M  # eq. 6 fit
    )
    return N * (T * step + end) + N * query_flops  # + F(x_r) over the N clients


def fedzo_round_flops(s: dict, query_flops: float) -> float:
    """One FedZO round: T finite-difference steps of Q+1 queries per client."""
    N, d, T, q = s["n_clients"], s["dim"], s["local_steps"], s["q"]
    step = (q + 1) * query_flops + 2.0 * q * d + 2.0 * q * d  # probes, contraction
    return N * T * step + N * query_flops


def round_flops(algo: str, s: dict, query_flops: float) -> float:
    if algo == "fzoos":
        return fzoos_round_flops(s, query_flops)
    if algo == "fedzo":
        return fedzo_round_flops(s, query_flops)
    raise KeyError(f"no operation count for algorithm {algo!r}")


def roofline_share(work: dict, calls: float, seconds: float, peaks: dict) -> tuple:
    """(share, bound): the least time ``calls`` calls could take on the chip,
    the larger of operations over peak and bytes over bandwidth, over the
    measured time; ``bound`` names which of the two."""
    t_flops = calls * work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = calls * work["bytes"] / peaks["hbm_bytes_per_s"]
    t_min = max(t_flops, t_bytes)
    return t_min / seconds, ("compute" if t_flops >= t_bytes else "memory")

