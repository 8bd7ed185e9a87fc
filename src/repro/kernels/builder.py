"""Rank- and DAG-generic Pallas kernel builder — ONE streaming kernel.

A single builder emits the combined spatial/temporal-blocking kernel for

  * any grid rank with streaming axis 0 (1D: stream only; 2D: 1-D blocking
    in x; 3D: 2-D blocking in (y, x) — the paper's §3.1 layouts), and
  * any *DAG* of PE stages (:class:`~repro.programs.DagSpec`): one stencil
    (the classic S=1 temporal chain), a linear multi-stage chain, or a
    general stage DAG — fan-out, fan-in (multi-input combine stages),
    multi-field state — topologically unrolled ``par_time`` times per
    super-step (StencilFlow, arXiv:2010.15218).  Intermediates live only in
    the rolling VMEM windows: zero HBM round-trips.

Architecture (see DESIGN.md §2 and §2.5):

  * one rolling circular slab window per *producer* value (external field
    stream or unrolled entry) that other entries consume, sized by
    StencilFlow buffer-depth analysis (:func:`repro.programs.dag_layout`):
    ``max over consumer edges of (Lag_c + R_c) - Lag_p + 1`` slots of
    ``par_vec`` rows — which is the chain's ``2*ceil(rad/V)+1`` when
    producer and consumer are adjacent, and grows by exactly the lag
    *difference* where an edge skips levels (a diamond's short branch);
  * fan-out is one producer window tapped by several consumers (no copies);
    each consumer re-imposes *its own* blocked-axis BC on every slab it
    reads, and applies its stream-axis BC in its window reads;
  * entry ``e`` lags the stream head by ``Lag_e = max over inputs of Lag_p
    + R_e`` slabs (the per-PE ``rad``-row lag of the paper, generalized to
    DAG edges and vector slabs);
  * double-buffered async slab DMA per external field stream in, per field
    out; prefetch stops at the last real slab; the tick loop runs ``nslabs
    + max output lag`` ticks;
  * partial super-steps (``steps < par_time``): linear chains fuse the
    select into every entry (identical to the classic PE forwarding);
    general DAGs insert radius-0 *state* nodes per updated field selecting
    new-vs-previous value, so every field advances simultaneously and
    un-taken iterations forward exactly.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import precision
from repro.core.blocking import BlockGeometry, stream_extension
from repro.programs import DagSpec, dag_layout, unroll_dag


@functools.lru_cache(maxsize=None)
def _stream_shifts(kind: str, dom: int, ds: int) -> tuple:
    """The nonzero row shifts the stream-axis BC ``kind`` applies to tap
    offset ``ds`` over a ``dom``-row stream: for every real output row
    ``o``, the source row is ``map(o + ds)``, i.e. the interior row shifted
    by ``map(o + ds) - (o + ds)``.  Static, so the kernel selects among
    static window slices instead of gathering at traced positions."""
    if kind == "constant":
        return ()
    r = np.arange(dom) + ds
    m = r
    if kind == "reflect":
        p = max(2 * dom - 2, 1)
        m = np.mod(r, p)
        m = np.where(m >= dom, p - m, m)
    d = np.unique(np.clip(m, 0, dom - 1) - r)
    return tuple(int(x) for x in d if x)


def _dag_kernel(*refs, plan, lay, geom: BlockGeometry, ns: int, dom: int,
                sdtype=jnp.float32, dst: bool = False):
    # mixed precision (repro.core.precision): every VMEM buffer — windows,
    # DMA slabs — holds the STORAGE dtype ``sdtype``; stage arithmetic runs
    # in f32.  For bf16 that means: widen the concatenated window read (and
    # the aux slab) to f32, apply the stencil against the f32 coefficients,
    # round the result back to bf16 exactly once per entry — the same
    # once-per-stage-application rounding the oracle/engine implement.  For
    # f32 ``needs_cast`` is False and ZERO casts are emitted: the trace is
    # identical to the pre-bf16 kernel, bit for bit.
    needs_cast = precision.needs_accum_cast(sdtype)
    nb = geom.ndim - 1                       # blocked (trailing) dims
    V = geom.par_vec
    F = plan.n_streams
    multi = F > 1
    entries = plan.entries
    BS = geom.bsize
    CS = geom.csize
    P = geom.pad                             # per-axis (tile-aligned) halo
    radii, lags, wins = lay.radii, lay.lags, lay.wins
    HA = lay.aux_depth                       # aux window depth, in slabs
    nslabs = ns // V
    nticks = nslabs + lay.out_lag
    has_aux = any(e.stencil is not None and e.stencil.has_aux
                  for e in entries)
    blanks = (slice(None),) * nb

    # value ids that need a rolling window, in id order (streams first)
    win_ids = [v for v in range(F + len(entries)) if wins[v] > 0]
    # out producers: value id -> field indices it drains to
    out_of: dict = {}
    for kf, o in enumerate(plan.outputs):
        out_of.setdefault(o, []).append(kf)

    # --- unpack the positional refs (operands, output, scratch) -------------
    steps_ref, coeff_ref, gp_ref = refs[0], refs[1], refs[2]
    p = 3
    aux_ref = None
    if has_aux:
        aux_ref, p = refs[p], p + 1
    p += dst                  # the destination operand: aliased, never read
    out_ref, p = refs[p], p + 1
    win_refs, p = refs[p:p + len(win_ids)], p + len(win_ids)
    win_of = dict(zip(win_ids, win_refs))
    in_buf, in_sems, p = refs[p], refs[p + 1], p + 2
    aux_win = aux_buf = aux_sems = None
    if has_aux:
        aux_win, aux_buf, aux_sems = refs[p:p + 3]
        p += 3
    out_buf, out_sems = refs[p], refs[p + 1]

    starts = tuple(pl.program_id(d) * CS[d] for d in range(nb))
    steps = steps_ref[0, 0]
    # row offsets within a slab, broadcastable over a (V, *BS) slab
    iv = jax.lax.broadcasted_iota(jnp.int32, (V,) + (1,) * nb, 0)

    # --- per-stage coefficient dicts (shared across par_time repeats) -------
    # built at kernel top level: values read inside a pl.when branch must not
    # be reused by a later branch (cross-trace constants)
    cdicts = {}
    for e in entries:
        if e.stencil is not None and e.coeff_lo not in cdicts:
            cdicts[e.coeff_lo] = {
                name: coeff_ref[0, e.coeff_lo + ci]
                for ci, name in enumerate(e.stencil.coeff_names)}

    def coeffs_of(entry):
        return cdicts[entry.coeff_lo]

    # --- blocked-axis boundary re-imposition, per consuming entry's BC ------
    # Only grid-edge blocks act, and which blocks those are is static: block
    # i of axis ax holds the first real column at lo_i = P - i*CS and the
    # last at hi_i = d-1 + P - i*CS.  Band columns are therefore static
    # slices picked by the block index — no value-level dynamic slicing.
    pids = [pl.program_id(ax) for ax in range(nb)]
    iotas = [jax.lax.broadcasted_iota(jnp.int32, (V,) + BS, 1 + ax)
             for ax in range(nb)]
    los = tuple(p_ - pid * c for p_, pid, c in zip(P, pids, CS))
    his = tuple((d - 1) + p_ - pid * c
                for d, p_, pid, c in zip(geom.blocked_dims, P, pids, CS))
    edge_lo = [[(i, P[ax] - i * CS[ax]) for i in range(geom.bnum[ax])
                if P[ax] - i * CS[ax] >= 1] for ax in range(nb)]
    edge_hi = [[(i, hi) for i in range(geom.bnum[ax])
                for hi in [geom.blocked_dims[ax] - 1 + P[ax] - i * CS[ax]]
                if hi <= BS[ax] - 2] for ax in range(nb)]

    def _band(slab, ax, edges, off):
        """Column ``c_i + off`` of ``slab`` along blocked axis ``ax``, for
        whichever edge block ``(i, c_i)`` of ``edges`` this program is (a
        width-1 slab that broadcasts along the axis)."""
        band = None
        for i, c in edges:
            col = jax.lax.slice_in_dim(slab, c + off, c + off + 1,
                                       axis=1 + ax)
            band = col if band is None else jnp.where(pids[ax] == i, col,
                                                      band)
        return band

    def _reimpose_axis(slab, kind, ax, fill, rad):
        if kind == "periodic":
            # wrap-padded halos are exact translated copies: no re-imposition
            return slab
        lo, hi, iota = los[ax], his[ax], iotas[ax]
        if kind == "constant":
            slab = jnp.where(iota < lo, fill, slab)
            return jnp.where(iota > hi, fill, slab)
        elo, ehi = edge_lo[ax], edge_hi[ax]
        if kind == "reflect":
            # only the rad columns the consuming stencil taps past the edge
            # reach a real cell; column lo-k mirrors lo+k, hi+k mirrors hi-k
            for k in range(1, rad + 1):
                if elo:
                    slab = jnp.where(iota == lo - k, _band(slab, ax, elo, k),
                                     slab)
                if ehi:
                    slab = jnp.where(iota == hi + k,
                                     _band(slab, ax, ehi, -k), slab)
            return slab
        if elo:
            slab = jnp.where(iota < lo, _band(slab, ax, elo, 0), slab)
        if ehi:
            slab = jnp.where(iota > hi, _band(slab, ax, ehi, 0), slab)
        return slab

    def reclamp_for(entry):
        bc = entry.bc
        kinds = ("clamp",) * nb if bc is None else tuple(bc.kinds[1:])
        fill = 0.0 if bc is None else bc.value
        rad = 0 if entry.stencil is None else entry.stencil.radius

        def reclamp(slab):
            for ax in range(nb):
                slab = _reimpose_axis(slab, kinds[ax], ax, fill, rad)
            return slab
        return reclamp

    reclamps = [reclamp_for(e) for e in entries]

    # --- DMA plumbing --------------------------------------------------------
    in_idx = tuple(pl.ds(s, b) for s, b in zip(starts, BS))
    out_idx = tuple(pl.ds(s + p_, c) for s, p_, c in zip(starts, P, CS))

    def in_copy(kf, j, slot):
        src = jnp.clip(j, 0, nslabs - 1) * V
        lead = (kf,) if multi else ()
        return pltpu.make_async_copy(
            gp_ref.at[lead + (pl.ds(src, V),) + in_idx],
            in_buf.at[lead + (slot,)], in_sems.at[lead + (slot,)])

    def aux_copy(j, slot):
        src = jnp.clip(j, 0, nslabs - 1) * V
        return pltpu.make_async_copy(
            aux_ref.at[(pl.ds(src, V),) + in_idx],
            aux_buf.at[slot], aux_sems.at[slot])

    def out_copy(kf, j, slot):
        lead = (kf,) if multi else ()
        return pltpu.make_async_copy(
            out_buf.at[lead + (slot,)],
            out_ref.at[lead + (pl.ds(j * V, V),) + out_idx],
            out_sems.at[lead + (slot,)])

    def in_slab(kf, slot):
        return in_buf[((kf, slot) if multi else (slot,))]

    for kf in range(F):
        in_copy(kf, 0, 0).start()
    if has_aux:
        aux_copy(0, 0).start()

    def emit_out(vid, j, val):
        """Drain ``val`` (a compute slab) to every field this value id
        feeds: crop the compute columns, double-buffer, start the DMA."""
        for kf in out_of[vid]:
            oslot = j % 2

            @pl.when(j >= 2)
            def _(kf=kf, oslot=oslot):   # slot reuse: prior copy must drain
                out_copy(kf, j - 2, oslot).wait()

            crop = val[(slice(None),)
                       + tuple(slice(p_, p_ + c) for p_, c in zip(P, CS))]
            if multi:
                out_buf[kf, oslot] = crop
            else:
                out_buf[oslot] = crop
            out_copy(kf, j, oslot).start()

    def body(k, _):
        # wait input slab k; prefetch slab k+1 (both stop at the last real
        # slab — later ticks only drain the DAG, fetching nothing)
        slot = k % 2
        for kf in range(F):
            @pl.when(k <= nslabs - 1)
            def _(kf=kf):
                in_copy(kf, k, slot).wait()

            @pl.when(k + 1 <= nslabs - 1)
            def _(kf=kf):
                in_copy(kf, k + 1, (k + 1) % 2).start()

            @pl.when(k <= nslabs - 1)
            def _(kf=kf):
                # push the input slab into the stream's window (pre-padded
                # => BC-ok) and drain pass-through fields straight to out
                if wins[kf] > 0:
                    win_of[kf][(pl.ds((k % wins[kf]) * V, V),) + blanks] = (
                        in_slab(kf, slot))
                if kf in out_of:
                    emit_out(kf, k, in_slab(kf, slot))

        if has_aux:
            @pl.when(k <= nslabs - 1)
            def _():
                aux_copy(k, slot).wait()

            @pl.when(k + 1 <= nslabs - 1)
            def _():
                aux_copy(k + 1, (k + 1) % 2).start()

            @pl.when(k <= nslabs - 1)
            def _():
                aux_win[(pl.ds((k % HA) * V, V),) + blanks] = aux_buf[slot]

        # -- unrolled DAG: entry e computes slab k - Lag_e -------------------
        for i, entry in enumerate(entries):
            vid = F + i
            j = k - lags[vid]
            R = radii[i]

            @pl.when((j >= 0) & (j <= nslabs - 1))
            def _(i=i, entry=entry, vid=vid, j=j, R=R):
                def read_slab(pid, jj):
                    W = wins[pid]
                    return win_of[pid][(pl.ds((jj % W) * V, V),) + blanks]

                if entry.stencil is None:
                    # state node: select the updated value while this
                    # iteration is real, else forward the field's previous
                    # value (PE forwarding, generalized per field)
                    val = jnp.where(entry.iteration + 1 <= steps,
                                    read_slab(entry.inputs[0], j),
                                    read_slab(entry.inputs[1], j))
                else:
                    bc = entry.bc
                    kind_s = "clamp" if bc is None else bc.kinds[0]
                    fill = 0.0 if bc is None else bc.value
                    if needs_cast:
                        # the stream-axis constant fill is applied AFTER the
                        # widening cast: round it through storage (on host —
                        # np, not a traced op) so it equals the bf16 padding
                        # the other backends read
                        fill = float(np.asarray(fill, jnp.dtype(sdtype)))
                    rec = reclamps[i]

                    def cat_of(pid):
                        """Producer ``pid``'s slabs j-R..j+R in logical
                        order, each re-imposed under *this* entry's
                        blocked-axis BC.  Linear chains skip this entirely:
                        the stream window is pre-padded under stage 0's BC
                        and every other slab was re-imposed with the (sole)
                        consumer's BC at push time — the PR 6 chain
                        op-for-op."""
                        slabs = [read_slab(pid, j + o)
                                 for o in range(-R, R + 1)]
                        if not plan.linear:
                            slabs = [rec(s) for s in slabs]
                        cat = jnp.concatenate(slabs, axis=0)
                        # window READ cast: widen storage to the f32
                        # accumulation dtype before any arithmetic
                        return cat.astype(jnp.float32) if needs_cast else cat

                    def make_get(cat):
                        def stream_tap(ds_):
                            """(V, *BS) slab of stream rows ``j*V+ds_ ..``
                            with this entry's stream-axis BC applied per
                            row: clamp clips, reflect mirrors, constant
                            overrides out-of-domain rows with the fill;
                            periodic was materialized as a stream extension
                            by the wrapper.  In the domain's interior this
                            is the static slice of ``cat`` at ``R*V+ds_``;
                            rows past a stream end take the static slice
                            shifted by the (static) set of BC shifts."""
                            at = R * V + ds_
                            vals = cat[at:at + V]
                            rows = j * V + ds_ + iv
                            if kind_s == "constant":
                                oob = (rows < 0) | (rows > dom - 1)
                                return jnp.where(oob, fill, vals)
                            shifts = _stream_shifts(kind_s, dom, ds_)
                            if not shifts:
                                return vals
                            if kind_s == "reflect":
                                p_ = max(2 * dom - 2, 1)
                                m = jnp.mod(rows, p_)
                                m = jnp.where(m >= dom, p_ - m, m)
                            else:
                                m = rows
                            delta = jnp.clip(m, 0, dom - 1) - rows
                            for sg in shifts:
                                assert 0 <= at + sg <= 2 * R * V, (ds_, sg)
                                vals = jnp.where(delta == sg,
                                                 cat[at + sg:at + sg + V],
                                                 vals)
                            return vals

                        # tap memo: one window slice per distinct stream
                        # offset, one lane/sublane rotate per full offset
                        taps = {}
                        zero = (0,) * nb

                        def get(off):
                            ds_, db = off[0], tuple(off[1:])
                            tap = taps.get(tuple(off))
                            if tap is None:
                                tap = taps.get((ds_,) + zero)
                                if tap is None:
                                    tap = taps[(ds_,) + zero] = (
                                        stream_tap(ds_))
                                for ax, d in enumerate(db):
                                    if d:
                                        tap = jnp.roll(tap, -d, axis=1 + ax)
                                taps[tuple(off)] = tap
                            return tap
                        return get

                    cats = {}
                    for pid in entry.inputs:
                        if pid not in cats:
                            cats[pid] = make_get(cat_of(pid))
                    gets = [cats[pid] for pid in entry.inputs]

                    aux_slab = None
                    if entry.stencil.has_aux:
                        ja = jnp.clip(j, 0, nslabs - 1)
                        aux_slab = aux_win[(pl.ds((ja % HA) * V, V),)
                                           + blanks]
                        if needs_cast:
                            aux_slab = aux_slab.astype(jnp.float32)
                    val = entry.stencil.apply(
                        tuple(gets) if entry.stencil.arity > 1 else gets[0],
                        coeffs_of(entry), aux_slab)
                    if entry.fused_select:
                        # linear-chain PE forwarding: un-taken repeats
                        # forward their input slab unchanged
                        val = jnp.where(entry.iteration + 1 <= steps, val,
                                        gets[0]((0,) * geom.ndim))
                    if needs_cast:
                        # output cast: round to storage ONCE per entry (=
                        # per stage application) before the value re-enters
                        # a VMEM window or the output DMA buffer
                        val = val.astype(sdtype)

                if wins[vid] > 0:
                    # linear chains re-impose the sole consumer's (entry
                    # i+1's) blocked-axis BC at push time; DAG fan-out
                    # defers to read time, where each consumer applies its
                    # own (see cat_of)
                    stored = reclamps[i + 1](val) if plan.linear else val
                    win_of[vid][(pl.ds((j % wins[vid]) * V, V),) + blanks] = (
                        stored)
                if vid in out_of:
                    emit_out(vid, j, val)
        return 0

    jax.lax.fori_loop(0, nticks, body, 0)

    # drain outstanding output DMAs (last two slabs; nslabs is static)
    for kf in range(F):
        if nslabs >= 2:
            out_copy(kf, nslabs - 2, (nslabs - 2) % 2).wait()
        out_copy(kf, nslabs - 1, (nslabs - 1) % 2).wait()


#: scoped-VMEM limit handed to Mosaic (its default, 16 MiB on v5e, is below
#: what autotune admits): the scratch buffers, which autotune keeps within
#: ``Device.vmem_budget`` (32 MiB on v5e), plus the compiler's temporaries
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _scratch_shapes(dag: DagSpec, geom: BlockGeometry, sdtype, lay=None):
    """The kernel's scratch buffers, in the order ``_dag_kernel`` unpacks
    them: one rolling window per consumed producer value (buffer-depth
    sized), the input double buffer, the aux window and its double buffer,
    the output double buffer, and their DMA semaphores."""
    V, BS, CS = geom.par_vec, geom.bsize, geom.csize
    F = dag.n_fields
    if lay is None:
        lay = dag_layout(unroll_dag(dag, geom.par_time), V)
    scratch = [pltpu.VMEM((w * V,) + BS, sdtype) for w in lay.wins if w > 0]
    lead = (F,) if F > 1 else ()
    scratch += [pltpu.VMEM(lead + (2, V) + BS, sdtype),  # in dbl buffer
                pltpu.SemaphoreType.DMA(lead + (2,))]
    if any(st.has_aux for st, _, _ in dag.stages):
        scratch += [pltpu.VMEM((lay.aux_depth * V,) + BS, sdtype),
                    pltpu.VMEM((2, V) + BS, sdtype),
                    pltpu.SemaphoreType.DMA((2,))]
    scratch += [pltpu.VMEM(lead + (2, V) + CS, sdtype),  # out dbl buffer
                pltpu.SemaphoreType.DMA(lead + (2,))]
    return scratch


@functools.partial(jax.jit,
                   static_argnames=("dag", "geom", "interpret",
                                    "block_parallel"))
def superstep_chain(dag: DagSpec, geom: BlockGeometry, gp: jnp.ndarray,
                    coeffs_packed: jnp.ndarray, steps: jnp.ndarray,
                    aux_p: Optional[jnp.ndarray] = None,
                    interpret: bool = True,
                    block_parallel: bool = False,
                    dst: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """One super-step (<= ``par_time`` fused program iterations) of a stage
    DAG over the padded state ``gp`` (``(ns, *padded)`` for single-field
    programs, ``(F, ns, *padded)`` for multi-field), through the unrolled
    per-super-step value graph.  A single stencil is the one-stage
    ``chain_dag(((stencil, bc),))``.

    The name is the compiled program's: this jitted function is the HLO
    instruction around the ``pallas_call`` (``%superstep_chain.N``), which
    the benchmark's trace readers match by name (``perfbench/scopes.py``
    and the kernel metrics), so it keeps its chain-era name.

    ``gp``/``aux_p`` are BC-padded by the wrapper (``kernels/ops``) under
    stage 0's BC: blocked dims to ``bnum*csize + 2*halo``, the stream axis
    extended ``2*size_halo`` when periodic and padded up to a ``par_vec``
    multiple.  Returns the padded output (only compute columns/rows are
    meaningful).

    ``block_parallel`` opts the kernel grid into Megacore ("parallel"
    dimension semantics): blocks are independent by construction, so the
    result is bit-identical to the sequential grid.

    ``dst`` (``gp``'s shape and dtype) is an extra ``pl.ANY`` operand aliased
    to the output (``input_output_aliases``): the kernel writes into it and
    never reads it, so a super-step loop can alternate between two buffers
    instead of letting XLA copy a fresh output back into its carry.  Write
    coverage, the invariant that makes this safe: every block writes every
    stream row (the periodic stream extension and the ``par_vec`` tail rows
    included) of its compute columns ``[pad + i*csize, pad + (i+1)*csize)``,
    so the compute columns and the overhang are rewritten every super-step.
    What stays as ``dst`` held it — the leading ``pad`` and the trailing
    columns past ``pad + bnum*csize`` of each blocked axis — lies inside the
    padding strips the loop's halo refresh rewrites (from the real cells,
    ``kernels/ops._reclamp_padded``) before the next kernel reads the buffer,
    so values from two super-steps back never reach a result."""
    nb = geom.ndim - 1
    V = geom.par_vec
    F = dag.n_fields
    multi = F > 1
    if multi and gp.shape[0] != F:
        raise ValueError(f"multi-field program: leading axis {gp.shape[0]} "
                         f"!= {F} fields")
    ns = gp.shape[1] if multi else gp.shape[0]
    bc0 = dag.stages[0][1]
    dom = geom.stream_dim + 2 * stream_extension(geom, bc0)
    if ns != geom.stream_slabs(dom) * V:
        raise ValueError(
            f"padded stream extent {ns} != ceil({dom}/{V})*{V} "
            f"= {geom.stream_slabs(dom) * V}: the wrapper must pad the "
            f"stream axis to a slab multiple (kernels/ops._pad_blocked)")
    plan = unroll_dag(dag, geom.par_time)
    lay = dag_layout(plan, V)
    has_aux = any(st.has_aux for st, _, _ in dag.stages)

    # every VMEM buffer holds the STORAGE dtype (bf16 windows halve the
    # working set); the kernel widens reads to f32 for the stage arithmetic
    sdtype = gp.dtype
    kernel = functools.partial(_dag_kernel, plan=plan, lay=lay, geom=geom,
                               ns=ns, dom=dom, sdtype=sdtype,
                               dst=dst is not None)
    scratch = _scratch_shapes(dag, geom, sdtype, lay)
    operands = (coeffs_packed.reshape(1, -1), gp) + (
        (aux_p,) if has_aux else ()) + ((dst,) if dst is not None else ())
    steps_arr = jnp.asarray(steps, jnp.int32).reshape(1, 1)
    grid = geom.bnum if nb else (1,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * (len(operands) - 1),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        out_shape=jax.ShapeDtypeStruct(gp.shape, sdtype),
        # the destination is the last operand, after ``steps_arr``
        input_output_aliases={len(operands): 0} if dst is not None else {},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                ("parallel" if block_parallel else "arbitrary",) * len(grid)),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(steps_arr, *operands)
