"""Public jit'd wrappers for the Pallas kernels: padding, dtype plumbing,
interpret-mode dispatch (CPU container -> interpret=True; real TPU ->
compiled). This is the layer ``core.dispatch.pqs_dot`` calls for its
Pallas backend — callers outside kernels/ should go through ``pqs_dot``
rather than these wrappers, so every quantized matmul shares one
padding/selection policy.

Shape handling: all entry points accept arbitrary (M, N, K); inputs are
zero-padded up to block multiples and outputs sliced back. Zero partial
products are sign-neutral and additively inert at every stage (sort,
saturation, wraparound), so padding is exact for every accumulation
policy. For the global-sort policies the *pairing permutation* is
computed over the padded tile set — dispatch pads identically for the
jnp backend, so both backends realize the same order.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pruning import nm_compress
from repro.kernels import autotune
from repro.kernels import nm_spmm as _nm
from repro.kernels import quant_matmul as _qm
from repro.kernels import sorted_matmul as _sm
from repro.kernels import sorted_stream as _ss

POLICIES = _sm.SEQ_POLICIES + _sm.SORT_POLICIES
# the N:M compressed-storage kernel family tunes/blocks independently of
# the dense kernels (different VMEM mix: one-hot expand slab vs dense w)
NM_POLICIES = tuple(f"nm:{p}" for p in POLICIES)
# the fused activation-gather implementation is its own family again:
# its working set scales with G*n_keep (compressed), not G*m (dense),
# so the blocks that win differ from both the dense and expand kernels
NM_GATHER_POLICIES = tuple(f"nmg:{p}" for p in POLICIES)

# N:M kernel implementation selection (see resolve_nm_impl):
#   expand — one-hot expand the compressed slab to dense in VMEM and run
#            the dense kernel bodies (the bit-exactness oracle; full
#            dense-K MXU work, saves HBM bytes only)
#   gather — gather the kept activation entries per m-group and contract
#            only n_keep/m of the products (saves FLOPs; VPU-flavored)
#   auto   — gather wherever it can win, expand where it cannot
NM_IMPLS = ("auto", "expand", "gather")
# below this many groups the whole contraction is a handful of columns;
# expand's single dense dot beats gather's index arithmetic
GATHER_MIN_G = 8

# Largest K the compiled (non-interpret) LEGACY one-pass sort kernel may
# keep VMEM-resident: 8 * 128 * 4096 * 4 B = 16 MiB for the product cube.
# The two-pass streaming pipeline (kernels/sorted_stream.py) is bounded
# by its int8 operand slabs instead: bn * K bytes, so MAX_STREAM_K below.
MAX_RESIDENT_K = 4096
MAX_STREAM_K = 65536

SORT_IMPLS = ("auto", "onepass", "twopass")

# TPU lane width: a compiled block's K extent is a multiple of it (or the
# whole K), so a sort tile below it shares its block with its neighbours
LANES = 128

# Per-platform (bm, bn) defaults for policy_matmul, keyed by
# jax.default_backend(). The sort policies keep bm small: their product
# cube (one-pass) or working pair (two-pass) scales with bm, so
# M-blocking is the lever that keeps the footprint under budget. On TPU,
# bn rides the 128-lane dim and the stepwise policies want a full
# (8, 128) f32 tile; CPU interpret mode favors small blocks
# (python-loop grid — fewer, larger steps lose). This table is the seed
# and fallback for the measured autotuner (kernels/autotune.py,
# REPRO_PQS_AUTOTUNE=off|tune|readonly); REPRO_PQS_BLOCKS overrides
# everything — "bm,bn" for all policies, or per-policy entries like
# "sorted:8,128;wide:128,128" (policies without an entry fall through).
_BLOCK_TABLE: dict[str, dict[str, tuple[int, int]]] = {
    "tpu": {
        "wide": (128, 128),  # MXU dot: full systolic tile
        "clip": (8, 128),  # VPU stepwise: min f32 tile, K-streamed
        "wrap": (8, 128),
        "sorted": (8, 128),  # K fully resident: keep bm minimal
        "sorted_tiled": (8, 128),
        "sorted_tiled_seq": (8, 128),
        # nm: family — compressed slabs are ~n_keep/m of the dense bytes,
        # so bn can ride larger before the w slab dominates VMEM; the
        # stepwise policies keep the dense (8, 128) working tile
        "nm:wide": (128, 128),
        "nm:clip": (8, 128),
        "nm:wrap": (8, 128),
        "nm:sorted": (8, 128),
        "nm:sorted_tiled": (8, 128),
        "nm:sorted_tiled_seq": (8, 128),
        # nmg: family — gather kernels are VPU gather-multiply bound with
        # an n_keep/m-sized product set; wide still wants the big tile
        # (its reduce is one lane-sum), the stepwise policies keep the
        # minimal f32 tile
        "nmg:wide": (128, 128),
        "nmg:clip": (8, 128),
        "nmg:wrap": (8, 128),
        "nmg:sorted": (8, 128),
        "nmg:sorted_tiled": (8, 128),
        "nmg:sorted_tiled_seq": (8, 128),
    },
    # CPU/GPU run interpret mode; block shape only affects grid overhead
    "cpu": {"*": (8, 128)},
    "gpu": {"*": (8, 128)},
}


_BLOCKS_SYNTAX = (
    "REPRO_PQS_BLOCKS must be 'bm,bn' (two ints, all policies) or "
    "';'-separated per-policy entries 'policy:bm,bn' "
    "(e.g. \"sorted:8,128;wide:128,128\")"
)


def env_blocks(policy: str) -> tuple[int, int] | None:
    """The REPRO_PQS_BLOCKS override for ``policy``, or None.

    Accepts the bare ``"bm,bn"`` form (applies to every policy) and
    per-policy entries ``"sorted:8,128;wide:128,128"``; the two forms
    may be mixed (the bare entry becomes the default for policies
    without their own). Malformed input raises with the full syntax.
    """
    env = os.environ.get("REPRO_PQS_BLOCKS")
    if not env:
        return None
    default = None
    per_policy: dict[str, tuple[int, int]] = {}
    for entry in env.split(";"):
        entry = entry.strip()
        if not entry:
            continue
        name, _, pair = entry.rpartition(":")
        try:
            bm, bn = (int(v) for v in pair.split(","))
        except ValueError as e:
            raise ValueError(
                f"{_BLOCKS_SYNTAX}; bad entry {entry!r} in {env!r}"
            ) from e
        if name:
            known = POLICIES + NM_POLICIES + NM_GATHER_POLICIES
            if name not in known:
                raise ValueError(
                    f"{_BLOCKS_SYNTAX}; unknown policy {name!r} in {env!r} "
                    f"(expected one of {known})"
                )
            per_policy[name] = (bm, bn)
        else:
            default = (bm, bn)
    return per_policy.get(policy, default)


def default_blocks(policy: str, platform: str | None = None
                   ) -> tuple[int, int]:
    """(bm, bn) for a policy on the current (or given) platform."""
    env = env_blocks(policy)
    if env:
        return env
    table = _BLOCK_TABLE.get(platform or jax.default_backend(),
                             _BLOCK_TABLE["cpu"])
    return table.get(policy) or table.get("*") or (8, 128)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (and 1 for n <= 1: a K=1 dot is already
    bitonic-sortable — padding it to 2 would be pure waste)."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def padded_k(k: int, policy: str, k_tile: int) -> int:
    """The K length a policy's kernel actually accumulates over.

    ``sorted`` runs one bitonic stage over the whole axis (power of two);
    the tiled policies pad to a whole number of k_tile tiles; the
    unsorted policies need no K padding at all.
    """
    if policy == "sorted":
        return next_pow2(k)
    if policy in ("sorted_tiled", "sorted_tiled_seq"):
        return k + ((-k) % k_tile)
    return k


def _as_int8(a: jax.Array) -> jax.Array:
    """Narrow an integer carrier to the int8 the kernels stream.

    The TPU's MXU multiplies int8 (it has no int32 x int32 dot), and the
    two-pass sort pipeline's VMEM scales with its int8 slabs. Carriers
    hold int8 values by the ``pqs_dot`` contract, so the cast is lossless
    for every legitimate caller. A silently wrapped out-of-contract value
    would diverge from the jnp backend, so on concrete (non-traced)
    operands the contract is checked loudly; the check is one cheap
    reduction next to a matmul. Traced calls (jitted serving steps, whose
    carriers come from int8 quantizers) trust the contract.
    """
    if a.dtype == jnp.int8:
        return a
    if not isinstance(a, jax.core.Tracer):
        lo, hi = int(jnp.min(a)), int(jnp.max(a))
        if lo < -128 or hi > 127:
            raise ValueError(
                f"kernel carriers must hold int8 values (pqs_dot "
                f"contract); got range [{lo}, {hi}] in {a.dtype}. Use "
                "backend='jnp' for wider products."
            )
    return a.astype(jnp.int8)


def resolve_sort_impl(kp: int, interpret: bool,
                      sort_impl: str = "auto") -> str:
    """Which global-sort kernel serves a (padded-)K request.

    ``auto`` keeps the legacy one-pass kernel where it is known-good
    (K within MAX_RESIDENT_K) and switches to the two-pass streaming
    pipeline above it. Explicit ``onepass`` above the resident bound on
    a compiled path raises — that is the one case the old hard refusal
    still covers; ``twopass`` is refused only past MAX_STREAM_K (the
    int8 slab budget), interpret mode is unbounded.
    """
    if sort_impl not in SORT_IMPLS:
        raise ValueError(
            f"sort_impl must be one of {SORT_IMPLS}, got {sort_impl!r}")
    if sort_impl == "auto":
        sort_impl = "onepass" if kp <= MAX_RESIDENT_K else "twopass"
    if interpret:
        return sort_impl
    if sort_impl == "onepass" and kp > MAX_RESIDENT_K:
        raise ValueError(
            f"one-pass sort kernel needs K={kp} VMEM-resident, above the "
            f"compiled-kernel bound {MAX_RESIDENT_K}; use "
            "sort_impl='twopass' (default above the bound)"
        )
    if sort_impl == "twopass" and kp > MAX_STREAM_K:
        raise ValueError(
            f"two-pass sort pipeline keeps (bn, K) int8 slabs resident; "
            f"K={kp} exceeds MAX_STREAM_K={MAX_STREAM_K}; use "
            "policy='sorted_tiled_seq' (fully K-streaming) or "
            "backend='jnp'"
        )
    return sort_impl


def resolve_nm_impl(policy: str, g: int, n_keep: int, m_group: int,
                    nm_impl: str | None = None, compiled: bool = False
                    ) -> str:
    """Which N:M kernel implementation serves a compressed matmul.

    Explicit ``nm_impl`` (or ``REPRO_PQS_NM_IMPL``) wins; ``auto`` picks
    ``gather`` wherever the kept-product contraction can actually save
    work and falls back to ``expand`` when it cannot. A ``compiled``
    (TPU) call always resolves ``auto`` to ``expand`` and refuses
    ``gather``: the per-element gather does not lower to Mosaic.

    * ``n_keep >= m_group`` — dense-as-sparse storage: every product is
      kept, gathering reorders full-dense work for no gain;
    * ``policy == "wide"`` — the exact wide sum is a single dense MXU
      dot under expand; a VPU gather-multiply-reduce over n_keep/m of
      the products does not beat the systolic array until sparsity is
      far higher than N:M configurations provide;
    * ``g < GATHER_MIN_G`` — a handful of groups: gather's index
      arithmetic costs more than the few columns it skips.
    """
    impl = nm_impl
    if impl is None:
        impl = os.environ.get("REPRO_PQS_NM_IMPL", "auto").strip().lower()
        impl = impl or "auto"
    if impl not in NM_IMPLS:
        raise ValueError(
            f"nm_impl (REPRO_PQS_NM_IMPL) must be one of {NM_IMPLS}, "
            f"got {impl!r}"
        )
    if compiled and impl == "gather":
        raise ValueError(
            "nm_impl='gather' does not compile for TPU (its per-element "
            "activation gather has no Mosaic lowering); use 'expand' or "
            "'auto', or backend='jnp'"
        )
    if impl != "auto":
        return impl
    if compiled or n_keep >= m_group:
        return "expand"
    if policy == "wide":
        return "expand"
    if g < GATHER_MIN_G:
        return "expand"
    return "gather"


def _refuse_compiled_sort(policy: str) -> None:
    """The global-permutation kernels exist in interpret mode only: Mosaic
    refuses their whole-K walks (``dynamic_slice``), in-kernel ``sort``
    and (bm, bn, 1) tile-sum blocks. A chip never silently falls back to
    jnp or to the interpreter."""
    raise ValueError(
        f"policy={policy!r} has no compiled TPU kernel (the global-sort "
        "kernels run in interpret mode only); use a K-streaming policy "
        f"{_sm.SEQ_POLICIES} or backend='jnp'"
    )


def seq_block_k(policy: str, kp: int, k_tile: int,
                bk: int | None = None) -> int:
    """K depth of one K-streaming grid step.

    sorted_tiled_seq: the smallest whole number of sort tiles that fills
    the lane width (a tile never straddles a block); the others: the
    tuned ``bk`` or a bandwidth-friendly slab of up to 512.
    """
    if policy == "sorted_tiled_seq":
        return max(k_tile, LANES)
    return bk if bk is not None else min(512, next_pow2(kp))


def _blocks_for(policy, m, n, kp, runner, tracing, nm=None):
    """bm, bn, bk resolution: env override > autotune (when enabled) >
    static table. bk is only tunable for the free-depth seq policies.
    ``nm`` carries (m_group, n_keep, G) for the compressed families so
    the autotune cache keys on the work actually launched."""
    env = env_blocks(policy)
    if env:
        return env[0], env[1], None
    if autotune.mode() != "off":
        tuned = autotune.best_blocks(policy, m, n, kp, runner=runner,
                                     tracing=tracing, nm=nm)
        if tuned:
            return tuned
    dbm, dbn = default_blocks(policy)
    return dbm, dbn, None


def policy_matmul(
    x: jax.Array,  # (M, K) integer carrier
    w: jax.Array,  # (N, K) integer carrier
    *,
    policy: str = "wide",
    acc_bits: int = 16,
    k_tile: int = 256,
    rounds: int = 1,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    sort_impl: str = "auto",
    interpret: bool | None = None,
    census: bool = True,
) -> jax.Array:
    """(M, N) int32 under any accumulation policy, any shape.

    The single Pallas entry point behind ``core.dispatch.pqs_dot``:
    pads M/N/K to block multiples, picks the K-streaming kernel for
    order-preserving policies and a global-sort kernel (one-pass
    K-resident or two-pass streaming, ``sort_impl``) for the
    permutation ones, and slices the result back. ``bm``/``bn``/``bk``
    default to the measured-autotune winner when REPRO_PQS_AUTOTUNE is
    enabled, else the per-platform ``_BLOCK_TABLE`` entry
    (REPRO_PQS_BLOCKS overrides both — bare "bm,bn" or per-policy
    "sorted:8,128;wide:128,128").

    ``census=False`` is the certified route (`core.certify`): the caller
    holds a proof that no partial sum can reach the acc_bits caps, so
    the narrow policy's stepwise saturate bookkeeping — and the sort
    pipeline itself — is provably a no-op, and the request is served by
    the exact wide kernel body (one MXU dot, bit-identical BY THE PROOF
    to the stepwise narrow result). Meaningless without a certificate:
    an uncertified caller would silently lose the saturation semantics.
    """
    assert policy in POLICIES, policy
    if not census:
        policy = "wide"  # provably saturate-free -> exact wide body
    interpret = (not _on_tpu()) if interpret is None else interpret
    m, n = x.shape[0], w.shape[0]
    kp = padded_k(x.shape[1], policy, k_tile)
    if bm is None and bn is None:
        # the tuner only rules when the caller pinned NEITHER dimension:
        # a winner was measured as a (bm, bn, bk) unit, so grafting one
        # of its axes onto a caller-pinned other would apply (and cache)
        # a configuration that was never timed or fit-checked
        def _runner(cbm, cbn, cbk):
            return policy_matmul(
                x, w, policy=policy, acc_bits=acc_bits, k_tile=k_tile,
                rounds=rounds, bm=cbm, bn=cbn, bk=cbk,
                sort_impl=sort_impl, interpret=interpret,
            )

        bm, bn, abk = _blocks_for(policy, m, n, kp, _runner,
                                  tracing=isinstance(x, jax.core.Tracer))
        bk = abk if bk is None else bk
    elif bm is None or bn is None:
        dbm, dbn = default_blocks(policy)
        bm = dbm if bm is None else bm
        bn = dbn if bn is None else bn
    if policy in _sm.SORT_POLICIES:
        if not interpret:
            _refuse_compiled_sort(policy)
        impl = resolve_sort_impl(kp, interpret, sort_impl)
        xp = _pad_to(_pad_to(x, bm, 0), kp, 1)
        wp = _pad_to(_pad_to(w, kp, 1), bn, 0)
        if impl == "onepass":
            out = _sm.sort_matmul(
                xp, wp, policy=policy, acc_bits=acc_bits, k_tile=k_tile,
                rounds=rounds, bm=bm, bn=bn, interpret=interpret,
            )
        else:
            out = _ss.stream_sort_matmul(
                _as_int8(xp), _as_int8(wp), policy=policy,
                acc_bits=acc_bits, k_tile=k_tile, rounds=rounds,
                bm=bm, bn=bn, interpret=interpret,
            )
    else:
        bk = seq_block_k(policy, kp, k_tile, bk)
        xp = _pad_to(_pad_to(_pad_to(x, bm, 0), kp, 1), bk, 1)
        wp = _pad_to(_pad_to(_pad_to(w, kp, 1), bk, 1), bn, 0)
        out = _sm.seq_policy_matmul(
            _as_int8(xp), _as_int8(wp), policy=policy, acc_bits=acc_bits,
            rounds=rounds, bm=bm, bn=bn, bk=bk, k_tile=k_tile,
            interpret=interpret,
        )
    return out[:m, :n]


def partial_policy_matmul(
    x: jax.Array,  # (M, k_shards * k_local) integer carrier
    w: jax.Array,  # (N, k_shards * k_local) integer carrier
    *,
    k_shards: int,
    policy: str = "wide",
    acc_bits: int = 16,
    k_tile: int = 256,
    rounds: int = 1,
    bm: int | None = None,
    bn: int | None = None,
    sort_impl: str = "auto",
    interpret: bool | None = None,
    census: bool = True,
) -> jax.Array:
    """Per-K-shard partials of a K-sharded policy matmul: (M, N, k_shards).

    The caller (``core.dispatch``) pre-pads K so it splits into
    ``k_shards`` equal, policy-padded slices; shard s's slice is then
    accumulated by the UNCHANGED local kernel body (``policy_matmul``)
    over its k_local columns only. The partials are "unsaturated"
    *across* shards — no cross-shard combine or re-clamp happens here;
    merging them (up the static combine tree, with stepwise saturation,
    counting combine-step overflows) is the dispatch layer's job through
    ``core.sorted_accum.tree_combine`` / ``combine_schedule`` — the same
    schedule whether combined locally or as pairwise mesh exchanges.
    Each shard's K footprint is K/k_shards, which is what carries the
    sort kernels past ``MAX_STREAM_K`` total K.
    """
    if k_shards < 1 or x.shape[1] % k_shards:
        raise ValueError(
            f"K={x.shape[1]} does not split into k_shards={k_shards} "
            "equal slices (dispatch pads K before sharding)"
        )
    k_local = x.shape[1] // k_shards
    parts = [
        policy_matmul(
            x[:, s * k_local : (s + 1) * k_local],
            w[:, s * k_local : (s + 1) * k_local],
            policy=policy, acc_bits=acc_bits, k_tile=k_tile, rounds=rounds,
            bm=bm, bn=bn, sort_impl=sort_impl, interpret=interpret,
            census=census,
        )
        for s in range(k_shards)
    ]
    return jnp.stack(parts, axis=-1)


def nm_partial_policy_matmul(
    x: jax.Array,  # (M, k_shards * g_local * m_group) integer carrier
    values: jax.Array,  # (N, k_shards * g_local, n_keep) int8
    indices: jax.Array,  # (N, k_shards * g_local, n_keep) int32
    *,
    m_group: int,
    k_shards: int,
    policy: str = "wide",
    acc_bits: int = 16,
    k_tile: int = 256,
    rounds: int = 1,
    bm: int | None = None,
    bn: int | None = None,
    sort_impl: str = "auto",
    nm_impl: str | None = None,
    interpret: bool | None = None,
    census: bool = True,
) -> jax.Array:
    """``partial_policy_matmul`` on N:M compressed storage.

    K shards in units of whole groups (the caller pads G to a k_shards
    multiple with g_local * m_group a policy-padded length), so a
    shard's slab expand/gather never crosses a shard boundary and each
    slice runs the unchanged ``nm_policy_matmul`` body. ``nm_impl``
    selects expand vs gather per slice (``auto`` resolves against the
    LOCAL G, so very small shards may individually fall back to expand
    — bit-identical either way).
    """
    g = values.shape[1]
    if k_shards < 1 or g % k_shards:
        raise ValueError(
            f"G={g} does not split into k_shards={k_shards} whole-group "
            "slices (dispatch pads G before sharding)"
        )
    g_local = g // k_shards
    k_local = g_local * m_group
    parts = [
        nm_policy_matmul(
            x[:, s * k_local : (s + 1) * k_local],
            values[:, s * g_local : (s + 1) * g_local],
            indices[:, s * g_local : (s + 1) * g_local],
            m_group=m_group, policy=policy, acc_bits=acc_bits,
            k_tile=k_tile, rounds=rounds, bm=bm, bn=bn,
            sort_impl=sort_impl, nm_impl=nm_impl, interpret=interpret,
            census=census,
        )
        for s in range(k_shards)
    ]
    return jnp.stack(parts, axis=-1)


def nm_policy_matmul(
    x: jax.Array,  # (M, K) integer carrier, K <= G * m_group
    values: jax.Array,  # (N, G, n_keep) int8 compressed weights
    indices: jax.Array,  # (N, G, n_keep) int32 in-group positions
    *,
    m_group: int,
    policy: str = "wide",
    acc_bits: int = 16,
    k_tile: int = 256,
    rounds: int = 1,
    bm: int | None = None,
    bn: int | None = None,
    bg: int | None = None,
    sort_impl: str = "auto",
    nm_impl: str | None = None,
    interpret: bool | None = None,
    census: bool = True,
) -> jax.Array:
    """Every accumulation policy directly on N:M compressed storage.

    The sparse sibling of ``policy_matmul``: same (M, N) int32 contract,
    same padding discipline, but the weight operand never exists dense
    in HBM. Two implementations serve it (``nm_impl`` /
    ``REPRO_PQS_NM_IMPL``, resolved by ``resolve_nm_impl``):

    * ``expand`` one-hot expands (bn, bg, n_keep) slabs to dense blocks
      in VMEM and runs the unchanged dense kernel bodies — the
      bit-exactness oracle, full dense-K work;
    * ``gather`` gathers the kept activation entries per m-group and
      contracts only the (bm, bn, bg*n_keep) kept products — n_keep/m
      of the work, bit-identical by the zero-product prefix property
      (see ``kernels/nm_spmm.py``).

    Padding happens on the GROUP axis (G) instead of K: groups pad to
    ``bg`` blocks (tiled policies pin ``bg * m_group = k_tile`` so tile
    boundaries coincide with the dense kernels'), and zero-padded
    groups expand/gather to zero products — additively inert through
    every policy, so results are bit-identical to ``nm_decompress``
    followed by dense ``policy_matmul``. Blocks resolve under the
    ``nm:`` (expand) or ``nmg:`` (gather) kernel family
    (``REPRO_PQS_BLOCKS``, autotune, ``_BLOCK_TABLE``), keyed on the
    compressed geometry ``(m_group, n_keep, G)`` rather than dense K.

    ``census=False``: the certified route, exactly as on
    ``policy_matmul`` — a `core.certify` proof makes the stepwise
    saturation dead code, so the request reroutes to the wide body on
    the SAME compressed storage (N:M savings retained).
    """
    assert policy in POLICIES, policy
    if not census:
        policy = "wide"  # provably saturate-free -> exact wide body
    interpret = (not _on_tpu()) if interpret is None else interpret
    if values.shape != indices.shape:
        raise ValueError(
            f"values/indices shape mismatch: {values.shape} vs "
            f"{indices.shape}"
        )
    if values.ndim != 3:
        raise ValueError(f"expected (N, G, n_keep) slabs, got {values.shape}")
    m = x.shape[0]
    n, g, n_keep = values.shape
    k_dense = g * m_group
    if x.shape[1] > k_dense:
        raise ValueError(
            f"contraction mismatch: x has K={x.shape[1]} but the "
            f"compressed weights cover G*m = {g}*{m_group} = {k_dense}"
        )
    if policy in ("sorted_tiled", "sorted_tiled_seq") and (
        k_tile % m_group != 0
    ):
        raise ValueError(
            f"tiled policies need k_tile % m_group == 0 so tile "
            f"boundaries align with the compressed groups; got "
            f"k_tile={k_tile}, m_group={m_group}"
        )
    kp = padded_k(k_dense, policy, k_tile)
    impl = resolve_nm_impl(policy, g, n_keep, m_group, nm_impl,
                           compiled=not interpret)
    fam = f"nmg:{policy}" if impl == "gather" else f"nm:{policy}"
    if bm is None and bn is None:

        def _runner(cbm, cbn, cbg):
            return nm_policy_matmul(
                x, values, indices, m_group=m_group, policy=policy,
                acc_bits=acc_bits, k_tile=k_tile, rounds=rounds,
                bm=cbm, bn=cbn, bg=cbg, sort_impl=sort_impl,
                nm_impl=impl, interpret=interpret,
            )

        bm, bn, abg = _blocks_for(fam, m, n, kp, _runner,
                                  tracing=isinstance(x, jax.core.Tracer),
                                  nm=(m_group, n_keep, g))
        bg = abg if bg is None else bg
    elif bm is None or bn is None:
        dbm, dbn = default_blocks(fam)
        bm = dbm if bm is None else bm
        bn = dbn if bn is None else bn
    xp = _pad_to(_pad_to(x, bm, 0), k_dense, 1)  # tail K -> whole groups
    vp = _pad_to(values, bn, 0)
    ip = _pad_to(indices, bn, 0)
    if policy in _sm.SORT_POLICIES:
        if not interpret:
            _refuse_compiled_sort(policy)
        simpl = resolve_sort_impl(kp, interpret, sort_impl)
        if policy == "sorted_tiled":
            # pad G so the compressed groups cover exactly kp columns —
            # the tiled kernels then never need an in-kernel column pad
            gp = kp // m_group
            if gp > g:
                vp = jnp.pad(vp, ((0, 0), (0, gp - g), (0, 0)))
                ip = jnp.pad(ip, ((0, 0), (0, gp - g), (0, 0)))
        xp = _pad_to(xp, kp, 1)
        if simpl == "onepass":
            fn = (_nm.nm_gather_sort_matmul if impl == "gather"
                  else _nm.nm_sort_matmul)
            out = fn(
                xp, vp, ip, policy=policy, acc_bits=acc_bits,
                k_tile=k_tile, rounds=rounds, m_group=m_group,
                bm=bm, bn=bn, interpret=interpret,
            )
        else:
            fn = (_ss.nm_gather_stream_sort_matmul if impl == "gather"
                  else _ss.nm_stream_sort_matmul)
            out = fn(
                _as_int8(xp), vp, ip, policy=policy, acc_bits=acc_bits,
                k_tile=k_tile, rounds=rounds, m_group=m_group,
                bm=bm, bn=bn, interpret=interpret,
            )
    else:
        if impl == "gather":
            if policy == "sorted_tiled_seq":
                bg = k_tile // m_group  # the sort block IS the k_tile
            elif bg is None:
                bg = max(1, min(512, next_pow2(k_dense)) // m_group)
        else:
            # the expand block: whole sort tiles (seq_block_k) and a
            # sublane-aligned number of groups (8 | bg)
            bk = seq_block_k(policy, k_dense, k_tile,
                             None if bg is None else bg * m_group)
            bg = math.lcm(bk, 8 * m_group) // m_group
        g_pad = (-g) % bg
        if g_pad:
            vp = jnp.pad(vp, ((0, 0), (0, g_pad), (0, 0)))
            ip = jnp.pad(ip, ((0, 0), (0, g_pad), (0, 0)))
            xp = _pad_to(xp, (g + g_pad) * m_group, 1)
        if impl == "gather":
            out = _nm.nm_gather_seq_policy_matmul(
                xp, vp, ip, policy=policy, acc_bits=acc_bits, rounds=rounds,
                m_group=m_group, bm=bm, bn=bn, bg=bg, interpret=interpret,
            )
        else:
            out = _nm.nm_seq_policy_matmul(
                _as_int8(xp), vp, ip, policy=policy, acc_bits=acc_bits,
                rounds=rounds, m_group=m_group, bm=bm, bn=bn, bg=bg,
                k_tile=k_tile, interpret=interpret,
            )
    return out[:m, :n]


def quant_matmul(x, w, *, bm=128, bn=128, bk=512, interpret=None):
    """Padded dense int8 matmul: (M,K) x (K,N) -> (M,N) int32."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    m, n = x.shape[0], w.shape[1]
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    out = _qm.quant_matmul(_as_int8(xp), _as_int8(wp), bm=bm, bn=bn, bk=bk,
                           interpret=interpret)
    return out[:m, :n]


def sorted_matmul(
    x, w, *, acc_bits=16, rounds=1, bm=None, bn=None, bk=256, interpret=None
):
    """PQS tiled-sort matmul: (M,K) x (N,K) -> (M,N) int32 @ acc_bits.

    Zero-padding is exact for the sort semantics: zero partial products are
    sign-neutral and additively inert at every stage.
    """
    return policy_matmul(
        x, w, policy="sorted_tiled_seq", acc_bits=acc_bits, k_tile=bk,
        rounds=rounds, bm=bm, bn=bn, interpret=interpret,
    )


def clip_matmul(x, w, *, acc_bits=16, bm=None, bn=None, bk=256,
                interpret=None):
    return policy_matmul(
        x, w, policy="clip", acc_bits=acc_bits, k_tile=bk,
        bm=bm, bn=bn, interpret=interpret,
    )


def nm_spmm(
    x, values, indices, *, m_group=16, bm=128, bn=128, bg=32, interpret=None
):
    """Compressed N:M matmul: (M,K) x [(N,G,keep) vals+idx] -> (M,N) int32,
    the exact ``wide`` policy on the expand kernel."""
    return nm_policy_matmul(
        x, values, indices, m_group=m_group, policy="wide", bm=bm, bn=bn,
        bg=bg, nm_impl="expand", interpret=interpret,
    )


def compress_nm_weights(w: np.ndarray, n_keep: int, m: int):
    """Host-side packer: dense (N, K) -> (values, indices) for nm_spmm."""
    vals, idx = nm_compress(np.asarray(w), n_keep, m)
    return jnp.asarray(vals, jnp.int8), jnp.asarray(idx, jnp.int32)
