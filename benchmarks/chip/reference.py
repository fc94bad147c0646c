"""Plain reference of the scheduler: the paper's Algorithm 1 and queue
update (7)-(8), written from arXiv 2209.02198 §IV and nothing of the
program (it imports no `repro` module and takes no array the program
made).

Per slot, with V, the intensities (Ce, Cc) and arrivals a observed:

- dispatch scores b[m, n] = V Ce pe[m] + Qc[m, n] - Qe[m]; type m goes
  to n1(m) = argmin_n b[m, n] (the first on ties);
- processing scores c[m, n] = V Cc[n] pc[m, n] - Qc[m, n];
- the edge, then each cloud n, fills its energy budget greedily: the
  items with a negative score, cheapest score per unit energy first
  (ties by index), each taking min(queue, floor(P / e)) tasks and
  paying that many times e from the budget P; the walk stops at the
  first item that no longer fits (fits == 0), as the pseudocode's
  `break`. The edge pays d * pe (the printed `floor(P/pe) * pe` is read
  as the typo it is; the clouds' line pays w * pc);
- emissions C = Ce sum d pe + sum_n Cc[n] sum_m w pc;
- Qe' = max(Qe - sum_n d, 0) + a, Qc' = max(Qc - w, 0) + d.

The walk is one item at a time, every row together, in the order of one
full sort; nothing is chunked or sorted partially. `dtype` sets the precision of every quantity: the
program states float32, and bfloat16 is the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fill(score, e, cap, budget, block=32):
    """Greedy fill of R rows at once: score, e, cap [R, K], budget [R].
    Returns the takes [R, K]. Step j visits each row's j-th item in its
    order, `block` steps to a loop trip; the takes are kept in that
    order and put back at the end."""
    R, K = score.shape
    Kp = -(-K // block) * block
    inf = jnp.asarray(jnp.inf, score.dtype)
    key = jnp.where(score < 0, score / e, inf)
    order = jnp.argsort(key, axis=-1, stable=True)
    pad = ((0, Kp - K), (0, 0))
    e_s = jnp.pad(jnp.take_along_axis(e, order, axis=-1).T, pad,
                  constant_values=1)                       # [Kp, R]
    cap_s = jnp.pad(jnp.take_along_axis(cap, order, axis=-1).T, pad)
    n_neg = jnp.sum(score < 0, axis=-1)
    zero = jnp.zeros((), score.dtype)

    def cond(c):
        j0, _, stopped, _ = c
        return jnp.any(~stopped & (j0 < n_neg))

    def body(c):
        j0, P, stopped, taken = c
        e_b = jax.lax.dynamic_slice_in_dim(e_s, j0, block)
        cap_b = jax.lax.dynamic_slice_in_dim(cap_s, j0, block)
        takes = []
        for i in range(block):
            live = ~stopped & (j0 + i < n_neg)
            fits = jnp.floor(P / e_b[i])
            t = jnp.where(live & (fits > 0), jnp.minimum(cap_b[i], fits),
                          zero)
            P = P - t * e_b[i]
            stopped = stopped | (live & (fits <= 0))
            takes.append(t)
        taken = jax.lax.dynamic_update_slice_in_dim(
            taken, jnp.stack(takes), j0, 0)
        return j0 + block, P, stopped, taken

    c0 = (jnp.int32(0), budget.astype(score.dtype), jnp.zeros((R,), bool),
          jnp.zeros((Kp, R), score.dtype))
    taken = jax.lax.while_loop(cond, body, c0)[3][:K]
    rows = jnp.arange(R)[:, None]
    return jnp.zeros_like(score).at[rows, order].set(taken.T)


def slot(Qe, Qc, pe, pc, Pe, Pc, Ce, Cc, a, V):
    """One slot: returns (Qe', Qc', emissions, dispatched, processed)."""
    M, N = Qc.shape
    Ve = V * Ce
    b_mn = (Ve * pe)[:, None] + Qc - Qe[:, None]
    n1 = jnp.argmin(b_mn, axis=1)
    b = jnp.min(b_mn, axis=1)
    c = (V * Cc)[None, :] * pc - Qc
    takes = fill(
        jnp.concatenate([b[None], c.T]),
        jnp.concatenate([pe[None], pc.T]),
        jnp.concatenate([Qe[None], Qc.T]),
        jnp.concatenate([jnp.reshape(Pe, (1,)), Pc]),
    )
    d = jnp.zeros_like(Qc).at[jnp.arange(M), n1].set(takes[0])
    w = takes[1:].T
    em = Ce * jnp.sum(d * pe[:, None]) + jnp.sum(Cc * jnp.sum(w * pc, 0))
    Qe2 = jnp.maximum(Qe - jnp.sum(d, axis=1), 0) + a
    Qc2 = jnp.maximum(Qc - w, 0) + d
    return Qe2, Qc2, em, jnp.sum(d), jnp.sum(w)


def run(pe, pc, Pe, Pc, V, carbon, arrivals, T, dtype=jnp.float32,
        Q0=None, t0=0, t_end=None):
    """T slots, t0 .. t0 + T - 1, from the queues Q0 = (Qe, Qc) (empty by
    default); slots from `t_end` on leave the queues as they are, so a
    run can go in chunks of one length and one compiled program.
    `carbon(t) -> (Ce, Cc)` and `arrivals(t) -> a` are the cell's
    generators. Returns the queues after the last slot and the per-slot
    series emissions, backlog (Qe + Qc after the slot), dispatched and
    processed, each [T]."""
    cast = lambda x: jnp.asarray(x).astype(dtype)  # noqa: E731
    pe, pc, Pe, Pc, V = map(cast, (pe, pc, Pe, Pc, V))
    M, N = pc.shape

    def body(Q, t):
        Ce, Cc = carbon(t)
        Qe, Qc, em, disp, proc = slot(*Q, pe, pc, Pe, Pc, cast(Ce),
                                      cast(Cc), cast(arrivals(t)), V)
        if t_end is not None:
            live = t < t_end
            Qe, Qc = jnp.where(live, Qe, Q[0]), jnp.where(live, Qc, Q[1])
        return (Qe, Qc), (em, jnp.sum(Qe) + jnp.sum(Qc), disp, proc)

    if Q0 is None:
        Q0 = (jnp.zeros((M,), dtype), jnp.zeros((M, N), dtype))
    Q0 = tuple(cast(q) for q in Q0)
    (Qe, Qc), series = jax.lax.scan(body, Q0, t0 + jnp.arange(T))
    return Qe, Qc, series
