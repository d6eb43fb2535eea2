"""Fused gossip-round megakernel (DESIGN.md §15).

Two ops, three realizations each, executing an entire event batch of the
scenario engines in one pass:

``round_step`` — the MP gossip round (``simulate.engines._scenario_scan``
round body) over a *flat* slot table ``Ke (n*k, p+1)``: column ``p`` is an
id column that records the index of the event that last wrote the slot.
Each round scatters ``[message | event-id]`` rows at the encoded targets
``enc = row*k + slot`` (undelivered events ride at the ``n*k`` sentinel and
are OOB-dropped), then reads the id column back: an event "keeps" exactly
when its own id survived, which identifies the true scatter winner under
duplicate (row, slot) targets regardless of the backend's collision policy.
The receiver update is *telescoped*: since Eq. 6 is affine in the slot
aggregate, the winner contributes ``a_i w_is (msg - k_old)`` to its row via
one masked scatter-add — no slot re-gather, no einsum, no argsort, which is
where the >= 1.5x CPU events/s win comes from (BENCH_network_sim.json).
On a row's *first* receipt the op first swaps in ``theta_base = f(K0)``
(the Eq. 6 image of the warm-start slots): the engine warm-starts theta at
the solitary models (paper §3.2), so the telescoped sum needs the affine
base once — exact because a row's slots cannot change before its first
receipt.  ``got_ever`` carries that per-row flag across rounds.

Contract (scheduler conformance): the op assumes delivery implies an
active receiver — ``simulate.scheduler.draw_events`` masks deliveries at
dead endpoints — so it never consults an ``active`` vector.  Feeding it
deliveries to inactive rows updates them anyway.

The engine overlaps rounds with a software-pipelined prefetch
(:func:`round_prefetch`): round t+1's messages and pre-scatter slot values
``k_old`` are gathered at the *end* of round t, after t's scatters — a
gather of old state held live across that state's scatter forces XLA's
copy insertion and pessimizes the scatter into a full-array expansion
(~mss per round on CPU), which the post-scatter placement avoids.

``cl_edge_step`` — the CL-ADMM edge phase (payload selection under
staleness, ``admm_edge_halfstep`` math, four OOB-masked slot scatters).  The
``reference`` and ``xla`` registrations share one callable whose expressions
mirror ``simulate.engines._cl_scenario_scan`` line for line, so routing the
engine through dispatch is bit-for-bit; the Pallas variant is the TPU
megakernel.

Pallas layout (both kernels): grid ``(2, n_event_blocks)`` — the last grid
dimension is the sequential TPU dimension, so every phase-0 block runs
before any phase-1 block, giving the same "all communication lands before
any update reads" barrier the engines rely on.  State arrays use full-array
BlockSpecs with constant index maps (fetched into VMEM once, written back
once at the end); event columns are tiled ``(block_b, 1)`` per grid step so
the pipeline double-buffers the next block's fetch behind the current
block's compute (the ``@pl.when`` idiom of ``kernels/flash_attention.py``).
Events are processed sequentially inside a block (``fori_loop``), which
resolves duplicate (row, slot) scatter targets in event order — the one
place the Pallas realization may pick a different duplicate winner than
XLA's scatter (both are valid realizations of the unordered batch, and the
id column keeps each realization self-consistent; see
tests/test_round_fuse.py).

Whole-state-in-VMEM is the operating point, and it bounds both kernels to
small states.  The v5e compiler (tests/test_chip_compile.py) accepts
``round_step_pallas`` at n=1024, k=8, p=64 and ``cl_edge_step_pallas`` at
n=1024, k=8, p=64 (17 MB of operands); it refuses n=10,000, k=8, p=32 for
``round_step`` (39 MB of scoped VMEM against its 16 MiB limit) and
n=4096, k=8, p=32 for ``cl_edge_step`` (86 MB).  Larger states belong to
the fused-XLA impl — the auto choice — or the sharded engines.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# Flat slot-table layout helpers
# ---------------------------------------------------------------------------


def encode_slots(K):
    """(n, k, p) slot table -> flat (n*k, p+1) with the id column at -1."""
    n, k, p = K.shape
    flat = K.reshape(n * k, p)
    return jnp.concatenate(
        [flat, jnp.full((n * k, 1), -1.0, flat.dtype)], axis=1)


def decode_slots(Ke, k):
    """Flat (n*k, p+1) -> the (n, k, p) slot table (id column dropped)."""
    nk, p1 = Ke.shape
    return Ke[:, : p1 - 1].reshape(nk // k, k, p1 - 1)


def round_scales(nbr_p, c, *, alpha: float):
    """Flat (n*k,) per-slot Eq. 6 gain ``a_i * w_is`` with
    ``a_i = alpha / (alpha + (1 - alpha) c_i)`` — the factor a slot delta
    carries into its row's model under the telescoped update."""
    a = alpha / (alpha + (1.0 - alpha) * c)
    return (a[:, None] * nbr_p).reshape(-1)


def round_stale_src(theta_prev, ev_i, ev_j):
    """(2B, p) sender rows of the *previous* model for one event batch.

    The stale-message source for :func:`round_prefetch`.  The engine
    gathers it *before* round t's theta scatter consumes ``theta_prev``
    (ordering pinned with an ``optimization_barrier``): once this gather is
    the buffer's last read, XLA updates theta in place instead of copying
    the full model table every round.
    """
    return theta_prev[jnp.concatenate([ev_i, ev_j])]


def round_prefetch(theta, theta_prev, Ke, ev_i, ev_j, ev_s, ev_r,
                   d_ij, d_ji, st_ij, st_ji, *, stale_src=None,
                   no_stale=False):
    """Gather one event batch's ``round_step`` operands.

    Returns ``(msg, tgt_row, enc, k_old)`` for the 2B directed sends
    (i->j slot r first, then j->i slot s, matching the engine's scatter
    order): the sender models (``theta_prev`` where stale), the receiver
    rows (``n`` where undelivered), the encoded flat targets (``n*k``
    sentinel where undelivered), and the pre-scatter slot values.  Call it
    *after* the round whose ``Ke`` it reads has scattered (the engine calls
    it at the end of round t for round t+1) — gathering ahead of a pending
    scatter on the same buffer defeats XLA's in-place scatter on CPU.

    ``stale_src`` optionally supplies :func:`round_stale_src`'s gather,
    already taken before the round's theta scatter (``theta_prev`` is then
    ignored) — the pipelined engine's in-place-theta arrangement.
    ``no_stale=True`` (a static fact about the scenario: zero staleness)
    skips the previous-model gather and select outright; the stale masks
    are all-False then, so the result is unchanged.
    """
    n, p = theta.shape
    nk = Ke.shape[0]
    km = nk // n
    send = jnp.concatenate([ev_i, ev_j])
    if no_stale:
        msg = theta[send]
    else:
        stale = jnp.concatenate([st_ij, st_ji])
        if stale_src is None:
            stale_src = theta_prev[send]
        msg = jnp.where(stale[:, None], stale_src, theta[send])
    tgt_row = jnp.concatenate([jnp.where(d_ij, ev_j, n),
                               jnp.where(d_ji, ev_i, n)])
    tgt_slot = jnp.concatenate([ev_r, ev_s])
    enc = jnp.where(tgt_row < n,
                    jnp.minimum(tgt_row, n - 1) * km + tgt_slot, nk)
    k_old = Ke[jnp.minimum(enc, nk - 1), :p]
    return msg, tgt_row, enc, k_old


# ---------------------------------------------------------------------------
# Fused-XLA round_step (CPU/GPU default): id-column dedup + telescoped theta
# ---------------------------------------------------------------------------


def round_step_xla(theta, Ke, got_ever, msg, tgt_row, enc, k_old,
                   theta_base, a_w):
    """Fused MP round over the flat slot table (see module docstring).

    Two flat scatters land ``[msg | id]`` (two halves of ~B rows each beat
    one 2B-row scatter on CPU); the id read-back picks the winners; ONE
    row scatter-add applies the telescoped deltas with the first-receipt
    base swap folded in as a ``theta_base - theta`` correction (a second
    scalar id scatter picks one first-receipt winner per row, so the
    correction lands exactly once even when a row's first round delivers
    into several slots).  Returns ``(theta, Ke, got_ever, keep)`` with
    ``keep`` the per-event winner mask (exactly one True per landed
    (row, slot) target).

    The first-receipt machinery (the base swap and the ``got_ever``
    update) is gated behind a runtime ``lax.cond`` on
    ``all(got_ever)``: once every row has received a message the
    correction is identically zero, and steady-state rounds run only the
    telescoped scatter-add — ~25% cheaper on CPU at n=10k.  The warm
    branch computes exactly what the ungated body did, so results are
    bitwise identical either way.
    """
    n = theta.shape[0]
    nk, p1 = Ke.shape
    p = p1 - 1
    m = msg.shape[0]
    half = m // 2
    ids = jnp.arange(m, dtype=Ke.dtype)               # exact in f32: m < 2^24
    payload = jnp.concatenate([msg, ids[:, None]], axis=1)
    # scatter: winner dedup downstream — the id column records which
    # duplicate landed; `keep` (below) reads it back, so any scatter order
    # yields a consistent winner
    Ke = Ke.at[enc[:half]].set(payload[:half], mode="drop")
    Ke = Ke.at[enc[half:]].set(payload[half:], mode="drop")  # scatter: winner dedup
    enc_c = jnp.minimum(enc, nk - 1)
    keep = (tgt_row < n) & (Ke[enc_c, p] == ids)
    row_c = jnp.minimum(tgt_row, n - 1)
    srow = jnp.where(keep, tgt_row, n)
    delta = jnp.where(keep, a_w[enc_c], 0.0)[:, None] * (msg - k_old)

    def _warm(got_ever):
        first = keep & ~got_ever[row_c]
        frow = jnp.where(first, tgt_row, n)
        # scatter: winner dedup downstream — first_w reads back which
        # duplicate first-receipt event landed in fid
        fid = jnp.zeros((n,), Ke.dtype).at[frow].set(ids, mode="drop")
        first_w = first & (fid[row_c] == ids)
        base_corr = jnp.where(first_w, 1.0, 0.0)[:, None] * (
            theta_base[row_c] - theta[row_c]
        )
        # scatter: idempotent (every value is True)
        return delta + base_corr, got_ever.at[frow].set(True, mode="drop")

    def _steady(got_ever):
        return delta, got_ever

    # the cond returns only the (2B, p) update payload — theta itself
    # stays outside the branches, so its scatter still runs in place
    upd, got_ever = jax.lax.cond(jnp.all(got_ever), _steady, _warm,
                                 got_ever)
    theta = theta.at[srow].add(upd, mode="drop")
    return theta, Ke, got_ever, keep


# ---------------------------------------------------------------------------
# Pallas round_step megakernel (TPU)
# ---------------------------------------------------------------------------


def _load_row(ref, i):
    """(X, p) ref -> row i as (p,)."""
    return ref[pl.ds(i, 1), :][0]


def _load_slot(ref, i, s):
    """(X, k, p) ref -> slot (i, s) as (p,)."""
    return ref[pl.ds(i, 1), pl.ds(s, 1), :][0, 0]


def _load_scalar(ref, i, s=None):
    """(X, 1) or (X, k) ref -> scalar at (i[, s])."""
    if s is None:
        return ref[pl.ds(i, 1), :][0, 0]
    return ref[pl.ds(i, 1), pl.ds(s, 1)][0, 0]


def _store_row(ref, i, val):
    ref[pl.ds(i, 1), :] = val[None]  # scatter: unique targets


def _store_slot(ref, i, s, val):
    ref[pl.ds(i, 1), pl.ds(s, 1), :] = val[None, None]  # scatter: unique targets


def _store_scalar(ref, i, s, val):
    ref[pl.ds(i, 1), pl.ds(s, 1)] = val[None, None]  # scatter: unique targets


def _mp_round_kernel(theta_ref, ke_ref, got_ref, msg_ref, row_ref, enc_ref,
                     kold_ref, base_ref, aw_ref,
                     theta_o, ke_o, got_o, keep_o, *, nk: int, block_b: int):
    ph = pl.program_id(0)
    bi = pl.program_id(1)
    f32 = jnp.float32

    @pl.when((ph == 0) & (bi == 0))
    def _init():
        theta_o[...] = theta_ref[...]
        ke_o[...] = ke_ref[...]
        got_o[...] = got_ref[...]

    @pl.when(ph == 0)
    def _land():
        # sequential per-event scatter of [msg | id]: duplicates resolve in
        # event order, and the surviving id names the winner for phase 1
        def body(e, carry):
            g = bi * block_b + e
            encv = enc_ref[e, 0]
            landed = encv < nk
            slot = jnp.where(landed, encv, 0)
            new = jnp.concatenate([_load_row(msg_ref, e),
                                   g.astype(f32)[None]])
            cur = _load_row(ke_o, slot)
            _store_row(ke_o, slot, jnp.where(landed, new, cur))
            return carry
        jax.lax.fori_loop(0, block_b, body, 0)

    @pl.when(ph == 1)
    def _update():
        p = base_ref.shape[1]

        def body(e, carry):
            g = bi * block_b + e
            encv = enc_ref[e, 0]
            landed = encv < nk
            slot = jnp.where(landed, encv, 0)
            win = landed & (_load_scalar(ke_o, slot, p) == g.astype(f32))
            _store_scalar(keep_o, g, 0, win.astype(jnp.int32))
            row = jnp.where(win, row_ref[e, 0], 0)
            go = _load_scalar(got_o, row) != 0
            first = win & ~go
            th = _load_row(theta_o, row)
            th = jnp.where(first, _load_row(base_ref, row), th)
            delta = jnp.where(win, _load_scalar(aw_ref, slot)
                              * (_load_row(msg_ref, e)
                                 - _load_row(kold_ref, e)), 0.0)
            _store_row(theta_o, row, th + delta)
            _store_scalar(got_o, row, 0, (go | win).astype(jnp.int32))
            return carry
        jax.lax.fori_loop(0, block_b, body, 0)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def round_step_pallas(theta, Ke, got_ever, msg, tgt_row, enc, k_old,
                      theta_base, a_w, *, block_b: int = 128,
                      interpret: bool = False):
    """Pallas megakernel over :func:`round_step_xla`'s signature.

    ``interpret`` is an explicit opt-in (CPU validation only); use
    ``kernels.dispatch`` for automatic selection.  See the module docstring
    for the grid/phase layout and the whole-state-in-VMEM sizing rule.
    """
    n, p = theta.shape
    nk = Ke.shape[0]
    m = msg.shape[0]
    block_b = max(1, min(block_b, m))
    pad = (-m) % block_b
    nb = (m + pad) // block_b

    def col(x, fill):
        # (2B,) event field -> padded (2B + pad, 1) int32; pads ride at the
        # sentinels (enc = n*k, row = n) so they are no-ops in both phases
        x = jnp.asarray(x).astype(jnp.int32)
        if pad:
            x = jnp.concatenate([x, jnp.full((pad,), fill, jnp.int32)])
        return x.reshape(-1, 1)

    def mat(x):
        x = jnp.asarray(x, jnp.float32)
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad, x.shape[1]), jnp.float32)])
        return x

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    full = lambda a: pl.BlockSpec(a.shape, lambda ph, bi, _nd=a.ndim:
                                  (0,) * _nd)
    ev_col = pl.BlockSpec((block_b, 1), lambda ph, bi: (bi, 0))
    ev_mat = pl.BlockSpec((block_b, p), lambda ph, bi: (bi, 0))
    args = (f32(theta), f32(Ke), got_ever.astype(jnp.int32).reshape(n, 1),
            mat(msg), col(tgt_row, n), col(enc, nk), mat(k_old),
            f32(theta_base), f32(a_w).reshape(nk, 1))
    kernel = functools.partial(_mp_round_kernel, nk=nk, block_b=block_b)
    theta_o, ke_o, got_o, keep_o = pl.pallas_call(
        kernel,
        grid=(2, nb),
        in_specs=[full(args[0]), full(args[1]), full(args[2]),
                  ev_mat, ev_col, ev_col, ev_mat,
                  full(args[7]), full(args[8])],
        out_specs=[full(args[0]), full(args[1]), full(args[2]),
                   pl.BlockSpec((m + pad, 1), lambda ph, bi: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, p), jnp.float32),
                   jax.ShapeDtypeStruct((nk, p + 1), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1), jnp.int32),
                   jax.ShapeDtypeStruct((m + pad, 1), jnp.int32)],
        interpret=interpret,
    )(*args)
    return (theta_o, ke_o, got_o[:, 0].astype(bool),
            keep_o[:m, 0].astype(bool))


# ---------------------------------------------------------------------------
# cl_edge_step — the CL-ADMM edge phase as one op
# ---------------------------------------------------------------------------


def cl_edge_step(theta, K, Z_own, Z_nbr, L_own, L_nbr,
                 pv_th, pv_K, pv_Lo, pv_Ln,
                 upd, own_s, oth_a, oth_s, stale, got, *, rho: float):
    """One batched CL-ADMM edge phase (scenario-engine semantics).

    theta (n, p) / K (n, k, p) are *post-primal*; Z/L slot arrays and the
    previous-round publish snapshot ``pv_*`` are round-start.  Per event
    side e: agent ``upd[e]`` updates its slot ``own_s[e]`` from partner
    ``oth_a[e]``'s payload (slot ``oth_s[e]``; ``stale`` selects the
    snapshot), scattered only where ``got`` (OOB-dropped otherwise).

    The expressions mirror ``simulate.engines._cl_scenario_scan`` line for
    line (payload selection, ``core.sparse.admm_edge_halfstep`` math, four
    masked scatters) — same compute graph, so the dispatch-routed engine's
    trajectory is bit-for-bit what the inline code produced.  Registered as
    both ``reference`` and ``xla``: the masked gather/scatter expression
    already lowers to one fused XLA program (same precedent as
    ``edge_reweight``).
    """
    n = theta.shape[0]
    stale_c = stale[:, None]
    th_pay = jnp.where(stale_c, pv_th[oth_a], theta[oth_a])
    k_pay = jnp.where(stale_c, pv_K[oth_a, oth_s], K[oth_a, oth_s])
    lo_pay = jnp.where(stale_c, pv_Lo[oth_a, oth_s], L_own[oth_a, oth_s])
    ln_pay = jnp.where(stale_c, pv_Ln[oth_a, oth_s], L_nbr[oth_a, oth_s])
    theta_own = theta[upd]
    k_own = K[upd, own_s]
    l_own = L_own[upd, own_s]
    l_nbr = L_nbr[upd, own_s]
    # core.sparse.admm_edge_halfstep, inlined to keep kernels/ free of a
    # core -> kernels -> core import cycle (expressions kept identical)
    z_own = 0.5 * ((l_own + ln_pay) / rho + theta_own + k_pay)
    z_nbr = 0.5 * ((lo_pay + l_nbr) / rho + th_pay + k_own)
    lo_new = l_own + rho * (theta_own - z_own)
    ln_new = l_nbr + rho * (k_own - z_nbr)
    rowu = jnp.where(got, upd, n)
    # scatter: unique targets — each event side writes its own (agent, slot)
    # cell; a slot belongs to one edge and each edge fires once per round
    Z_own = Z_own.at[rowu, own_s].set(z_own, mode="drop")
    Z_nbr = Z_nbr.at[rowu, own_s].set(z_nbr, mode="drop")  # scatter: unique targets
    L_own = L_own.at[rowu, own_s].set(lo_new, mode="drop")  # scatter: unique targets
    L_nbr = L_nbr.at[rowu, own_s].set(ln_new, mode="drop")  # scatter: unique targets
    return Z_own, Z_nbr, L_own, L_nbr


def _cl_edge_kernel(theta_ref, K_ref, Zo_ref, Zn_ref, Lo_ref, Ln_ref,
                    pth_ref, pK_ref, pLo_ref, pLn_ref,
                    av, sv, ov, tv, stv, gv,
                    Zo_o, Zn_o, Lo_o, Ln_o,
                    zo_scr, zn_scr, lo_scr, ln_scr, *,
                    rho: float, block_b: int):
    ph = pl.program_id(0)
    bi = pl.program_id(1)

    @pl.when((ph == 0) & (bi == 0))
    def _init():
        Zo_o[...] = Zo_ref[...]
        Zn_o[...] = Zn_ref[...]
        Lo_o[...] = Lo_ref[...]
        Ln_o[...] = Ln_ref[...]

    @pl.when(ph == 0)
    def _compute():
        # every half-step reads round-start refs only -> no hazard; results
        # park in scratch until all of phase 0 has run
        def body(e, carry):
            g = bi * block_b + e
            a = av[e, 0]
            so = sv[e, 0]
            o = ov[e, 0]
            ot = tv[e, 0]
            stl = stv[e, 0] != 0
            th_pay = jnp.where(stl, _load_row(pth_ref, o),
                               _load_row(theta_ref, o))
            k_pay = jnp.where(stl, _load_slot(pK_ref, o, ot),
                              _load_slot(K_ref, o, ot))
            lo_pay = jnp.where(stl, _load_slot(pLo_ref, o, ot),
                               _load_slot(Lo_ref, o, ot))
            ln_pay = jnp.where(stl, _load_slot(pLn_ref, o, ot),
                               _load_slot(Ln_ref, o, ot))
            theta_own = _load_row(theta_ref, a)
            k_own = _load_slot(K_ref, a, so)
            l_own = _load_slot(Lo_ref, a, so)
            l_nbr = _load_slot(Ln_ref, a, so)
            z_own = 0.5 * ((l_own + ln_pay) / rho + theta_own + k_pay)
            z_nbr = 0.5 * ((lo_pay + l_nbr) / rho + th_pay + k_own)
            _store_row(zo_scr, g, z_own)
            _store_row(zn_scr, g, z_nbr)
            _store_row(lo_scr, g, l_own + rho * (theta_own - z_own))
            _store_row(ln_scr, g, l_nbr + rho * (k_own - z_nbr))
            return carry
        jax.lax.fori_loop(0, block_b, body, 0)

    @pl.when(ph == 1)
    def _scatter():
        def body(e, carry):
            g = bi * block_b + e
            ok = gv[e, 0] != 0
            row = jnp.where(ok, av[e, 0], 0)
            slot = jnp.where(ok, sv[e, 0], 0)
            for scr, out in ((zo_scr, Zo_o), (zn_scr, Zn_o),
                             (lo_scr, Lo_o), (ln_scr, Ln_o)):
                old = _load_slot(out, row, slot)
                _store_slot(out, row, slot,
                            jnp.where(ok, _load_row(scr, g), old))
            return carry
        jax.lax.fori_loop(0, block_b, body, 0)


@functools.partial(jax.jit, static_argnames=("rho", "block_b", "interpret"))
def cl_edge_step_pallas(theta, K, Z_own, Z_nbr, L_own, L_nbr,
                        pv_th, pv_K, pv_Lo, pv_Ln,
                        upd, own_s, oth_a, oth_s, stale, got, *,
                        rho: float, block_b: int = 128,
                        interpret: bool = False):
    """Pallas realization of :func:`cl_edge_step` (same signature).

    Grid ``(2, n_event_blocks)``: phase 0 computes every half-step from
    round-start state into VMEM scratch, phase 1 lands the masked scatters —
    the same all-reads-before-any-write barrier the XLA form gets from
    functional updates.
    """
    n, k, p = K.shape
    E = upd.shape[0]
    block_b = max(1, min(block_b, E))
    pad = (-E) % block_b
    nb = (E + pad) // block_b

    def col(x):
        x = jnp.asarray(x).astype(jnp.int32)
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), jnp.int32)])
        return x.reshape(-1, 1)

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    full = lambda a: pl.BlockSpec(a.shape, lambda ph, bi, _nd=a.ndim:
                                  (0,) * _nd)
    ev_spec = pl.BlockSpec((block_b, 1), lambda ph, bi: (bi, 0))
    args = (f32(theta), f32(K), f32(Z_own), f32(Z_nbr), f32(L_own),
            f32(L_nbr), f32(pv_th), f32(pv_K), f32(pv_Lo), f32(pv_Ln),
            col(upd), col(own_s), col(oth_a), col(oth_s), col(stale),
            col(got))
    kernel = functools.partial(_cl_edge_kernel, rho=rho, block_b=block_b)
    slot_shape = jax.ShapeDtypeStruct((n, k, p), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(2, nb),
        in_specs=[full(a) for a in args[:10]] + [ev_spec] * 6,
        out_specs=[full(K)] * 4,
        out_shape=[slot_shape] * 4,
        scratch_shapes=[pltpu.VMEM((E + pad, p), jnp.float32)] * 4,
        interpret=interpret,
    )(*args)
