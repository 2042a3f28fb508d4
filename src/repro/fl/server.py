"""Server-side round logic (paper Algs. 1, 3, 6, 7).

``fl_round`` composes the full Alg. 6 pipeline around an algorithm-registry
triple (``core.algorithms.get_algorithm``):

  broadcast -> algorithm.client_update (H local steps; FedProx proximal
  term / SCAFFOLD control correction live here) -> client EF-compress(delta)
  -> masked aggregate -> optional downlink EF-compress ->
  algorithm.server_update (avg | slowmo | fedadam | fedyogi | scaffold-c).

All message-space state is flat: per-client EF error is an (N, D) matrix,
downlink EF a (D,) vector, and SCAFFOLD's per-client control variates an
(N, D) matrix (``FLState.ctrl``) with the server control variate as the
algorithm state — the scan-carry layout of the compiled engine, which for a
chunked client pass holds the per-client rows as (m, chunk, D) blocks.
Compression comes from ``core.compression.get_compressor`` (registry names +
traced :class:`CompressionParams`); the old opaque-callable compressor and
the per-leaf EF branch were removed after their deprecation release.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import aggregation as agg
from repro.core import chunking
from repro.core.algorithms import registry as algorithms
from repro.core.algorithms.registry import Algorithm, AlgoParams
from repro.core.compression import error_feedback
from repro.core.compression import registry as compression_lib
from repro.core.compression.error_feedback import SparseEF
from repro.core.compression.registry import CompressionParams, CompressorFn
from repro.core.privacy import registry as privacy_lib
from repro.core.privacy.registry import Privacy, PrivacyParams

PyTree = Any

# re-exported here for callers that sized payloads off the server module
flat_dim = algorithms.flat_dim


def flatten_clients(tree: PyTree) -> Tuple[jnp.ndarray, Callable]:
    """Stacked (N, ...) leaves -> one (N, D) float32 message matrix, plus the
    inverse (which restores shapes and dtypes). Shared message-space layout
    of the flat-FL and hierarchical-FL engines (fl/runtime.py)."""
    leaves, treedef = jax.tree.flatten(tree)
    n = leaves[0].shape[0]
    flat = jnp.concatenate(
        [leaf.astype(jnp.float32).reshape(n, -1) for leaf in leaves], axis=1)

    def unflatten(mat: jnp.ndarray) -> PyTree:
        out, off = [], 0
        for leaf in leaves:
            size = leaf[0].size
            out.append(mat[:, off:off + size]
                       .reshape(leaf.shape).astype(leaf.dtype))
            off += size
        return jax.tree.unflatten(treedef, out)

    return flat, unflatten


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FLState:
    params: PyTree
    client_error: Any  # (N, D) uplink EF matrix | SparseEF (N, S) | None;
    #                    (m, chunk) rows in the engine's chunked pass
    server_error: Optional[jnp.ndarray]   # (D,) downlink EF state, or None
    server_opt: Any    # algorithm server state: SlowMoState | ServerOptState
    #                    | (D,) SCAFFOLD server control variate | None
    ctrl: Optional[jnp.ndarray] = None    # (N, D) SCAFFOLD client variates
    round: int = 0


def default_ef_slots(d: int) -> int:
    """Default sparse-EF slot count: 2x the default 1% top-k budget, so the
    truncated residual has headroom around the kept coordinates."""
    return min(d, max(1, d // 50))


def init_fl_state(params: PyTree, n_clients: int, *,
                  algo: Union[str, Algorithm] = "fedavg",
                  use_ef: bool = False, double_ef: bool = False,
                  server: Optional[str] = None, ef_mode: str = "dense",
                  ef_slots: Optional[int] = None, state_dtype=jnp.float32,
                  n_rows: Union[int, Tuple[int, int], None] = None
                  ) -> FLState:
    """``use_ef`` allocates the per-client EF state, ``double_ef`` the (D,)
    downlink EF vector; the algorithm decides its own server state and
    whether an (N, D) control-variate matrix joins the carry.

    Fleet-scale knobs: ``ef_mode="sparse"`` stores the EF matrix as a
    :class:`SparseEF` of ``ef_slots`` (value, index) pairs per client
    (O(N·S), top-k compressor family); ``state_dtype`` (fp32/bf16) is the
    storage dtype of the message-space client state (EF values and SCAFFOLD
    control variates — compute stays fp32); ``n_rows`` sizes the
    per-client state for the chunked client pass: the chunk-padded row
    count, or the blocked ``(m, chunk)`` row shape, which keeps each
    block's rows contiguous (defaults to ``n_clients``)."""
    if server is not None:
        warnings.warn(
            "init_fl_state(server=...) is deprecated; pass algo="
            "<algorithm registry name> instead", DeprecationWarning,
            stacklevel=2)
        algo = algorithms.from_server_name(server)
    if ef_mode not in ("dense", "sparse"):
        raise ValueError(f"unknown ef_mode {ef_mode!r}; use 'dense'/'sparse'")
    a = algorithms.get_algorithm(algo)
    d = flat_dim(params)
    rows = n_clients if n_rows is None else n_rows
    rows = rows if isinstance(rows, tuple) else (rows,)
    if use_ef and ef_mode == "sparse":
        slots = default_ef_slots(d) if ef_slots is None else ef_slots
        client_error = error_feedback.init_sparse_error(rows, d, slots,
                                                        state_dtype)
    elif use_ef:
        client_error = jnp.zeros((*rows, d), state_dtype)
    else:
        client_error = None
    server_error = jnp.zeros(d, jnp.float32) if double_ef else None
    ctrl = jnp.zeros((*rows, d), state_dtype) if a.uses_ctrl else None
    return FLState(params, client_error, server_error,
                   a.init_algo_state(params), ctrl, 0)


def _resolve_algo(algo, aparams, lr, server, server_lr, slowmo_beta, momentum
                  ) -> Tuple[Algorithm, AlgoParams]:
    """Resolve the algorithm + params, mapping the deprecated stringly-typed
    kwargs (one release) onto the registry."""
    legacy = {"lr": lr, "server": server, "server_lr": server_lr,
              "slowmo_beta": slowmo_beta, "momentum": momentum}
    if any(v is not None for v in legacy.values()):
        given = sorted(k for k, v in legacy.items() if v is not None)
        warnings.warn(
            f"fl_round({'/'.join(given)}=...) is deprecated; pass "
            "algo=<registry name> + aparams=AlgoParams(...) instead "
            "(core.algorithms.get_algorithm)", DeprecationWarning,
            stacklevel=3)
        algo_name = algorithms.get_algorithm(algo).name
        if server is not None:
            mapped = algorithms.from_server_name(server)
            if algo_name not in ("fedavg", mapped):
                raise ValueError(
                    f"fl_round sets both algo={algo_name!r} and the "
                    f"deprecated server={server!r} (-> {mapped!r}); drop "
                    "server=")
            algo = algo_name = mapped
        if momentum is not None:
            # the old path always ran momentum-SGD clients; only the
            # fedavg_m client update reads AlgoParams.momentum
            if algo_name == "fedavg":
                algo = "fedavg_m"
            elif algo_name != "fedavg_m":
                raise ValueError(
                    f"fl_round(momentum=...) has no registry equivalent for "
                    f"algo={algo_name!r} (its client update ignores "
                    "momentum); compose your own Algorithm triple instead")
        ap = aparams if aparams is not None else algorithms.default_algo_params()
        updates = {k: jnp.float32(v) for k, v in legacy.items()
                   if v is not None and k != "server"}
        aparams = ap._replace(**updates)
    a = algorithms.get_algorithm(algo)
    return a, (aparams if aparams is not None
               else algorithms.default_algo_params())


def fl_round(state: FLState, stacked_batches, loss_fn, *,
             algo: Union[str, Algorithm] = "fedavg",
             aparams: Optional[AlgoParams] = None,
             participation: Optional[jnp.ndarray] = None,
             compress_fn: Optional[CompressorFn] = None,
             cparams: Optional[CompressionParams] = None,
             key: Optional[jax.Array] = None,
             compression_name: Optional[str] = None,
             chunk_size: Optional[int] = None,
             n_clients: Optional[int] = None,
             staleness_weights: Optional[jnp.ndarray] = None,
             privacy: Optional[Union[str, Privacy]] = None,
             pparams: Optional[PrivacyParams] = None,
             privacy_key: Optional[jax.Array] = None,
             gate_ef: bool = False, guard_empty: bool = False,
             lr=None, server=None, server_lr=None, slowmo_beta=None,
             momentum=None) -> Tuple[FLState, Dict[str, jnp.ndarray]]:
    """One FL round.

    ``stacked_batches``: a pytree with (N, H, ...) leaves, or a callable
    ``ids -> pytree`` with (len(ids), H, ...) leaves (on-device data
    generation; requires ``n_clients``). ``chunk_size`` (a power of two)
    processes clients in blocks via a ``lax.scan`` — peak temporary memory
    O(chunk·D) instead of O(N·D) — and is *bitwise* equivalent to the
    unchunked pass: every cross-client reduction goes through the canonical
    pairwise tree (``core.chunking.canonical_sum``) and all per-client
    randomness is keyed by ``fold_in(key, client_id)``, both invariant to
    how clients are batched. The bitwise guarantee holds when both rounds
    run under ``jax.jit`` (the engine always does): eagerly, XLA
    constant-folds transcendentals (e.g. QSGD's ``log2``) with a different
    evaluator than the compiled scan program, costing the last ulp. With
    ``chunk_size`` the per-client state (EF/ctrl) must be allocated with
    ``init_fl_state(n_rows=(m, chunk))``, ``m = ceil(N/chunk)`` (the
    engine's layout), or flat with ``n_rows=m * chunk``; the round returns
    it in the layout it was given.

    The algorithm *name* is static; every hyperparameter rides the traced
    ``aparams`` (a vmappable sweep axis). Registry compression
    (``compress_fn``/``cparams``/``key``) flattens each client's delta into
    one message, applies EF in message space (dense, or truncated-sparse /
    bf16 when the state was allocated that way), and reports the
    participation-weighted ``metrics["uplink_bits"]`` — control-variate
    algorithms uplink a second message-sized payload (the ctrl delta), which
    is compressed and billed the same way. Passing ``compression_name``
    routes large client passes (``N·D >= registry.KERNEL_DISPATCH_MIN_ELEMS``)
    through the kernel row APIs (real Pallas on TPU). The old ``lr=``/
    ``server=``/``server_lr=``/``slowmo_beta=``/``momentum=`` kwargs are
    deprecated and map onto the registry for one release.

    Failure-aware hooks (the fault engine's degradation semantics):
    ``staleness_weights`` (N,) multiplies each client's *wire* message
    before the aggregation sum only — EF accrues the true residual and the
    participation mask stays a select, so an all-ones weight vector is
    bitwise identical to passing ``None`` (``x * 1.0`` is an IEEE-754
    identity). ``gate_ef`` freezes non-participating clients' EF rows (a
    dropped client's error state carries forward untouched instead of
    accruing against an update that never shipped). ``guard_empty``
    restores the pre-round params / server state / downlink EF when *no*
    client participates — an all-failed round is bitwise a no-op even for
    stateful server optimizers.

    Privacy (``core.privacy`` registry): ``privacy=`` names a mechanism
    (``secagg``/``dp``/``secagg_dp``), ``pparams`` carries the traced
    ``(clip, sigma, field_bits)``, and ``privacy_key`` seeds mask PRGs and
    DP noise (fold-tagged, chunk-invariant). The mechanism's
    ``client_transform`` runs on each client's *wire* message after
    EF/compression (clipping and field-quantization error are deliberately
    not EF-tracked — the residual the server never saw must not leak back
    into client state), pairwise masks over uint32 are added for the
    surviving cohort (they cancel mod ``2^32`` for any survivor set), and
    ``server_transform`` decodes the field sum / adds central DP noise
    before the participation-masked mean. Field modes report dense
    ``field_bits * d`` uplink bits (masked messages are incompressible) and
    are incompatible with ``staleness_weights`` and sparse position-coded
    compressors; any privacy bans control-variate (second-uplink)
    algorithms — all enforced with explicit errors.
    """
    a, ap = _resolve_algo(algo, aparams, lr, server, server_lr, slowmo_beta,
                          momentum)
    batch_fn = stacked_batches if callable(stacked_batches) else None
    if batch_fn is not None:
        if n_clients is None:
            raise ValueError("fl_round needs n_clients= when batches come "
                             "from a callable (on-device) generator")
        n = n_clients
    else:
        n = jax.tree.leaves(stacked_batches)[0].shape[0]
    d = flat_dim(state.params)
    comp_active = compress_fn is not None

    ef = state.client_error
    sparse_ef = isinstance(ef, SparseEF)
    if sparse_ef:
        state_dt, ef_slots = ef.values.dtype, ef.values.shape[-1]
    else:
        state_dt, ef_slots = (ef.dtype if ef is not None else jnp.float32), 0

    rows_fn = fused_sign = None
    if comp_active:
        with jax.named_scope("fl.compress"):
            k_up, k_down, k_ctrl = jax.random.split(key, 3)
        if compression_name is not None:
            # kernel dispatch keys on the FULL pass size N·D (a static,
            # trace-time fact), never the block size — chunked and unchunked
            # runs of one problem always take the same operator path
            rows_fn = compression_lib.rows_compressor(compression_name, n * d)
            fused_sign = (compression_name == "scaled_sign"
                          and ef is not None and not sparse_ef
                          and compression_lib.kernel_dispatch(
                              compression_name, n * d))
        else:
            rows_fn = jax.vmap(compress_fn, in_axes=(None, 0, 0))
    with jax.named_scope("fl.local_update"):
        c_tree = (algorithms.unflatten_vec(state.server_opt, state.params)
                  if a.uses_ctrl else None)
    with jax.named_scope("fl.aggregate"):
        part = (participation.astype(jnp.float32)
                if participation is not None else None)
        sw = (staleness_weights.astype(jnp.float32)
              if staleness_weights is not None else None)
    if gate_ef and part is None:
        raise ValueError("fl_round(gate_ef=True) needs participation= "
                         "(the gate freezes non-participants' EF rows)")

    priv = None
    if privacy is not None:
        priv = (privacy_lib.get_privacy(privacy) if isinstance(privacy, str)
                else privacy)
        if priv.name == "none":
            priv = None
    if priv is not None:
        if privacy_key is None:
            raise ValueError(
                f"fl_round(privacy={priv.name!r}) needs privacy_key= — mask "
                "PRG seeds and DP noise must be fresh every round")
        if pparams is None:
            pparams = privacy_lib.default_privacy_params()
        if a.uses_ctrl:
            raise ValueError(
                f"privacy={priv.name!r} does not cover algo={a.name!r}: the "
                "control-variate uplink would be a per-client plaintext "
                "side channel")
        if priv.uses_field and sw is not None:
            raise ValueError(
                f"privacy={priv.name!r} is incompatible with "
                "staleness_weights=: fractional weights cannot scale uint32 "
                "field elements")
        if (priv.uses_field and compression_name is not None
                and compression_name not in privacy_lib.FIELD_COMPATIBLE):
            raise ValueError(
                f"privacy={priv.name!r} cannot ship "
                f"compression={compression_name!r} messages through a masked "
                f"field sum; legal: {'/'.join(privacy_lib.FIELD_COMPATIBLE)}")
    mask_env = None
    if priv is not None and priv.uses_masks:
        with jax.named_scope("fl.privacy"):
            mask_env = _mask_prepass(privacy_key, n, d, part, chunk_size)

    # --- one block of the client pass (Alg. 6/7 lines 4-11) ---------------
    # Per-client work only: local updates, message flattening, EF +
    # compression, then canonical partial sums. Every client compresses
    # (and accrues EF error) whether or not it is scheduled; participation
    # gates the sums only (plus, under gate_ef, the EF advancement). The
    # unchunked pass is this function called once; the chunked pass gives
    # ``put_ef``, which writes the block's new EF into the carried state
    # and whose result is returned in its place.
    def client_block(ids, batches_b, part_b, sw_b, ef_b, ctrl_b,
                     put_ef=None):
        with jax.named_scope("fl.aggregate"):
            valid = (ids < n).astype(jnp.float32)
        with jax.named_scope("fl.local_update"):
            if a.uses_ctrl:
                ci_tree = algorithms.unflatten_rows(
                    ctrl_b.astype(jnp.float32), state.params)

                def one(b, ci):
                    return a.client_update(loss_fn, ap, state.params, b,
                                           (ci, c_tree))

                deltas, ctrl_deltas, losses = jax.vmap(one)(batches_b,
                                                            ci_tree)
            else:
                def one(b):
                    return a.client_update(loss_fn, ap, state.params, b,
                                           None)

                deltas, _, losses = jax.vmap(one)(batches_b)
        with jax.named_scope("fl.compress"):
            ctrl_flat = (flatten_clients(ctrl_deltas)[0] if a.uses_ctrl
                         else None)
            flat, _ = flatten_clients(deltas)        # (c, D) message space

            new_ef_b, ctrl_wire, bits = ef_b, ctrl_flat, None
            if comp_active:
                keys_up = chunking.client_keys(k_up, ids)
                if ef_b is None:
                    flat, bits = rows_fn(cparams, keys_up, flat)
                elif fused_sign:
                    flat, e_new = _kernel_sign_ef(flat,
                                                  ef_b.astype(jnp.float32))
                    new_ef_b = e_new.astype(state_dt)
                    bits = jnp.broadcast_to(compression_lib.uplink_bits_jax(
                        "scaled_sign", cparams, d), (flat.shape[0],))
                else:
                    e_dense = (error_feedback.densify_rows(ef_b, d)
                               if sparse_ef else ef_b.astype(jnp.float32))
                    corrected = flat + e_dense
                    flat, bits = rows_fn(cparams, keys_up, corrected)
                    resid = corrected - flat
                    new_ef_b = (error_feedback.sparsify_rows(resid, ef_slots,
                                                             state_dt)
                                if sparse_ef else resid.astype(state_dt))
                if ctrl_flat is not None:
                    # the control-variate delta is a second message on the
                    # same uplink: compressed with the same operator (no
                    # EF), billed
                    keys_c = chunking.client_keys(k_ctrl, ids)
                    ctrl_wire, cbits = rows_fn(cparams, keys_c, ctrl_flat)
                    bits = bits + cbits

        if gate_ef and comp_active and ef_b is not None:
            # dropped / failed clients' error state carries forward
            # untouched (their residual is not lost against an update that
            # never shipped); a row-select, so surviving rows stay bitwise
            with jax.named_scope("fl.client_state"):
                keep = (part_b != 0)
                new_ef_b = jax.tree.map(
                    lambda nw, old: jnp.where(
                        keep.reshape((-1,) + (1,) * (nw.ndim - 1)), nw, old),
                    new_ef_b, ef_b)

        if put_ef is not None:
            # the write-back goes before the block's sums, held there by a
            # barrier: XLA would otherwise schedule it after them and keep
            # the corrected (chunk, D) message alive through the sums (on
            # the TPU at D = 1.24e8, 0.2 GiB more scratch). The barrier
            # also ends the messages' concatenate before the sums: at
            # blocks of 1, XLA would fold the stacked partials' unit axis
            # into it, and the TPU compiler then relays each leaf by an
            # unrolled reshape instead of a copy (at D = 2.67e8, 0.18 GiB
            # more program)
            if new_ef_b is not None:
                new_ef_b = put_ef(new_ef_b)
            flat, ctrl_wire, new_ef_b = lax.optimization_barrier(
                (flat, ctrl_wire, new_ef_b))

        w = valid if part_b is None else part_b
        if priv is not None:
            # privacy acts on the wire message (post-EF/compression): clip,
            # field-encode, add local noise; then the cohort's pairwise
            # masks. Masks on non-survivor rows are garbage but harmless —
            # canonical_sum where-selects w == 0 rows away.
            with jax.named_scope("fl.privacy"):
                flat = priv.client_transform(pparams, privacy_key, ids, flat)
                if mask_env is not None:
                    gsum, cnt = mask_env
                    flat = flat + privacy_lib.pairwise_masks(
                        privacy_key, ids, d, gsum, cnt)
                if priv.uses_field and bits is not None:
                    # a masked field message is dense: field_bits per
                    # coordinate
                    bits = jnp.broadcast_to(
                        pparams.field_bits * jnp.float32(d), bits.shape)
        with jax.named_scope("fl.aggregate"):
            # staleness discount multiplies the *wire* message in the sum
            # only (EF above saw the true residual); all-ones weights are
            # bitwise the unweighted sum (x * 1.0 == x in IEEE-754)
            dsrc = flat if sw_b is None else flat * sw_b[:, None]
            psums = {"delta": chunking.canonical_sum(dsrc, w),
                     "loss": chunking.canonical_sum(losses, valid)}
            if bits is not None:
                psums["bits"] = chunking.canonical_sum(bits, w)
            if ctrl_wire is not None:
                psums["ctrl"] = chunking.canonical_sum(ctrl_wire, w)
        new_ctrl_b = ctrl_b
        if ctrl_wire is not None:
            # only scheduled clients advance their local control variate
            with jax.named_scope("fl.client_state"):
                new_ctrl_b = (ctrl_b.astype(jnp.float32)
                              + ctrl_wire * w[:, None]).astype(state_dt)
        return psums, new_ef_b, new_ctrl_b

    if chunk_size is not None and chunk_size < n:
        chunk = chunk_size
        m = chunking.n_blocks(n, chunk)
        npad = m * chunk
        ef_rows = _state_rows(ef, "client_error", ((m, chunk), (npad,)),
                              "chunk_size")
        ctrl_rows = _state_rows(state.ctrl, "ctrl", ((m, chunk), (npad,)),
                                "chunk_size")
        with jax.named_scope("fl.aggregate"):
            part_pad = (None if part is None
                        else jnp.pad(part, (0, npad - n)).reshape(m, chunk))
            sw_pad = (None if sw is None
                      else jnp.pad(sw, (0, npad - n)).reshape(m, chunk))
        # per-client state rides the scan as (m, chunk, ...): a block reads
        # its rows by an index on the leading, untiled axis and writes them
        # back in place, one contiguous slab. In a flat (npad, ...) array
        # on the TPU's tiled layout every tile holds rows of several
        # blocks, so each block's write would rewrite the whole state. The
        # engine allocates the blocked shape; a flat state (a direct
        # caller's) is reshaped here and back on the way out.
        @jax.named_scope("fl.client_state")
        def rows(st, b):
            return jax.tree.map(
                lambda x: lax.dynamic_index_in_dim(x, b, keepdims=False), st)

        @jax.named_scope("fl.client_state")
        def put_rows(st, new, b):
            return jax.tree.map(
                lambda x, y: lax.dynamic_update_index_in_dim(x, y, b, 0),
                st, new)

        def scan_block(carry, xs):
            ef_all, ctrl_all = carry
            b, part_b, sw_b = xs
            with jax.named_scope("fl.data"):
                ids = chunking.block_ids(b, chunk)
                batches_b = (batch_fn(ids) if batch_fn is not None
                             else jax.tree.map(lambda x: x[ids],
                                               stacked_batches))
            psums, new_ef_all, new_ctrl_b = client_block(
                ids, batches_b, part_b, sw_b, rows(ef_all, b),
                rows(ctrl_all, b), put_ef=lambda e: put_rows(ef_all, e, b))
            psums = {k: v[None] if v.ndim else v for k, v in psums.items()}
            return (new_ef_all, put_rows(ctrl_all, new_ctrl_b, b)), psums

        # the scan stacks each block's D-wide partials as a (1, D) row, so
        # the stack is (m, 1, D): the block index is a leading, untiled
        # axis, each block writes only its own slab and the fold below
        # reads whole slabs. Stacked as (m, D), every tile of the TPU's
        # tiled layout holds a row of each block, so each block's write
        # would rewrite the whole stack and the fold slice through it.
        # Work under no stage of its own is aggregation's.
        with jax.named_scope("fl.aggregate"):
            (client_error, new_ctrl), psums_m = lax.scan(
                scan_block, (_with_rows(ef, (m, chunk)),
                             _with_rows(state.ctrl, (m, chunk))),
                (jnp.arange(m, dtype=jnp.int32), part_pad, sw_pad))
        client_error = _with_rows(client_error, ef_rows)
        new_ctrl = _with_rows(new_ctrl, ctrl_rows)
        # block partials are aligned subtrees of the full canonical tree, so
        # folding them canonically reproduces the unchunked sum bit-for-bit
        with jax.named_scope("fl.aggregate"):
            totals = {k: chunking.canonical_sum(v) for k, v in psums_m.items()}
            totals = {k: v[0] if v.ndim else v for k, v in totals.items()}
    else:
        _state_rows(ef, "client_error", ((n,),), "the client count")
        _state_rows(state.ctrl, "ctrl", ((n,),), "the client count")
        with jax.named_scope("fl.data"):
            ids = jnp.arange(n, dtype=jnp.int32)
            batches = (batch_fn(ids) if batch_fn is not None
                       else stacked_batches)
        totals, client_error, new_ctrl = client_block(ids, batches, part, sw,
                                                      ef, state.ctrl)

    # --- aggregation (Alg. 6 line 12): participation-masked mean ----------
    with jax.named_scope("fl.aggregate"):
        nsched = jnp.sum(part) if part is not None else None
        denom = (jnp.float32(n) if part is None
                 else jnp.maximum(nsched, 1.0))
        tot_delta = totals["delta"]
    if priv is not None:
        # decode the modular field sum back to float / add central DP noise
        # (noise calibrated to the clipped per-client sensitivity, so it is
        # added to the *sum*, before the mean)
        with jax.named_scope("fl.privacy"):
            tot_delta = priv.server_transform(pparams, privacy_key,
                                              tot_delta)
    with jax.named_scope("fl.aggregate"):
        mean_delta = algorithms.unflatten_vec(tot_delta / denom,
                                              state.params)
        uplink_bits = totals.get("bits")

    with jax.named_scope("fl.server_update"):
        # --- downlink (PS-side) EF compression (Alg. 6 lines 15-17) ---
        server_error = state.server_error
        if comp_active and server_error is not None:
            corrected = algorithms.flatten_vec(mean_delta) + server_error
            c, _ = compress_fn(cparams, k_down, corrected)
            server_error = corrected - c
            mean_delta = algorithms.unflatten_vec(c, mean_delta)

        # --- control-variate bookkeeping (SCAFFOLD) ---
        # clients advance c_i by the *transmitted* (possibly compressed)
        # ctrl delta — the same quantity the server integrates into c — so
        # c = mean(c_i) stays consistent under lossy compression.
        ctrl_aux = None
        if a.uses_ctrl:
            part_frac = (jnp.float32(1.0) if part is None else nsched / n)
            ctrl_aux = (totals["ctrl"] / denom, part_frac)

        # --- server update (registry triple) ---
        new_params, new_opt = a.server_update(ap, state.params, mean_delta,
                                              state.server_opt, ctrl_aux)

        if guard_empty and part is not None:
            # graceful degradation: an all-failed round is bitwise a no-op
            # — the model, server optimizer state, and downlink EF all
            # carry forward (a zero mean delta is *not* enough:
            # momentum/Adam state and the fedbuff buffer counter would
            # still advance). Rounds with any survivor select the freshly
            # computed values elementwise, which is bitwise the unguarded
            # result.
            alive = nsched > 0
            new_params = jax.tree.map(
                lambda a_, b_: jnp.where(alive, a_, b_), new_params,
                state.params)
            new_opt = jax.tree.map(lambda a_, b_: jnp.where(alive, a_, b_),
                                   new_opt, state.server_opt)
            if server_error is not None:
                server_error = jnp.where(alive, server_error,
                                         state.server_error)

    with jax.named_scope("fl.log"):
        metrics = {"loss": totals["loss"] / n,
                   "delta_norm": _global_norm(mean_delta)}
    if uplink_bits is not None:
        metrics["uplink_bits"] = uplink_bits
    return FLState(new_params, client_error, server_error, new_opt,
                   new_ctrl, state.round + 1), metrics


def _mask_prepass(privacy_key: jax.Array, n: int, d: int,
                  part: Optional[jnp.ndarray], chunk_size: Optional[int]
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cohort aggregate every pairwise mask needs: ``(gsum, cnt)`` where
    ``gsum = sum_{j in S} g_j`` (uint32, wraps) and ``cnt = |S|`` over the
    survivor set S (participation != 0; all clients when ``part is None``).
    uint32 addition is exactly associative, so accumulating per chunk-sized
    block (O(chunk * D) memory, mirroring the client pass) is bitwise the
    one-shot sum for any blocking. PRG rows are regenerated in the main
    client pass — 2x PRG cost buys O(chunk * D) instead of O(N * D)."""
    if chunk_size is not None and chunk_size < n:
        chunk = chunk_size
        m = chunking.n_blocks(n, chunk)

        def body(carry, b):
            gs, cn = carry
            ids_b = chunking.block_ids(b, chunk)
            surv_b = ids_b < n
            if part is not None:
                surv_b &= part[jnp.minimum(ids_b, n - 1)] != 0
            g = privacy_lib.mask_rows(privacy_key, ids_b, d)
            gs = gs + jnp.sum(jnp.where(surv_b[:, None], g, jnp.uint32(0)),
                              axis=0, dtype=jnp.uint32)
            return (gs, cn + jnp.sum(surv_b.astype(jnp.uint32))), None

        (gsum, cnt), _ = lax.scan(
            body, (jnp.zeros(d, jnp.uint32), jnp.uint32(0)),
            jnp.arange(m, dtype=jnp.int32))
        return gsum, cnt
    ids = jnp.arange(n, dtype=jnp.int32)
    surv = jnp.ones(n, bool) if part is None else part != 0
    g = privacy_lib.mask_rows(privacy_key, ids, d)
    gsum = jnp.sum(jnp.where(surv[:, None], g, jnp.uint32(0)), axis=0,
                   dtype=jnp.uint32)
    return gsum, jnp.sum(surv.astype(jnp.uint32))


def _kernel_sign_ef(flat: jnp.ndarray, e: jnp.ndarray):
    """Fused scaled-sign + EF via the kernel row API (kernel-dispatch path
    only; deferred import keeps fl/server free of a hard kernels dep)."""
    from repro.kernels import ops as kernel_ops
    return kernel_ops.sign_ef_rows(flat, e)


def _state_rows(st, name: str, allowed, why: str):
    """The row shape (every axis but the last) of per-client state ``st``,
    checked against the ``allowed`` row shapes; None without state."""
    if st is None:
        return None
    got = jax.tree.leaves(st)[0].shape[:-1]
    if got not in allowed:
        raise ValueError(
            f"FLState.{name} has rows {got} but {why} requires "
            f"{' or '.join(map(str, allowed))}; allocate it with "
            "init_fl_state(n_rows=...) matching the chunked client pass")
    return got


def _with_rows(st, rows):
    """Per-client state with its row axes reshaped to ``rows`` (no-op when
    they already are)."""
    return jax.tree.map(lambda x: x.reshape(*rows, x.shape[-1]), st)


def _global_norm(tree: PyTree) -> jnp.ndarray:
    # summed through the canonical tree: a plain reduction's order depends
    # on what XLA fuses into it, which differs between the chunked and the
    # unchunked pass
    return jnp.sqrt(sum(
        chunking.canonical_sum(jnp.square(x.astype(jnp.float32)).ravel())
        for x in jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# PSSGD (Alg. 1): one synchronous gradient-averaging step
# ---------------------------------------------------------------------------
def pssgd_round(params: PyTree, stacked_batches: Dict[str, jnp.ndarray],
                loss_fn, *, lr: float, compression: str = "none",
                cparams: Optional[CompressionParams] = None,
                key: Optional[jax.Array] = None
                ) -> Tuple[PyTree, jnp.ndarray]:
    """theta <- theta - lr * mean_i g_i (eq. 6), with optional registry
    compression of each client's flattened gradient message."""
    def one(p, batch):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
        return g, loss
    grads, losses = jax.vmap(one, in_axes=(None, 0))(params, stacked_batches)
    if compression != "none":
        compress_fn = compression_lib.get_compressor(compression)
        if cparams is None:
            cparams = compression_lib.default_compression_params(
                flat_dim(params))
        if key is None:
            # a silently fixed key would reuse the same dither every round,
            # correlating the quantization error across steps
            raise ValueError(
                "pssgd_round needs key= when compression != 'none' "
                "(stochastic compressors must see fresh randomness each "
                "round)")
        flat, unflatten = flatten_clients(grads)
        keys = jax.random.split(key, flat.shape[0])
        comp, _ = jax.vmap(compress_fn, in_axes=(None, 0, 0))(
            cparams, keys, flat)
        grads = unflatten(comp)
    mean_g = agg.average_gradients(grads)
    new_params = jax.tree.map(
        lambda p, g: (p.astype(jnp.float32) - lr * g.astype(jnp.float32)).astype(p.dtype),
        params, mean_g)
    return new_params, jnp.mean(losses)
