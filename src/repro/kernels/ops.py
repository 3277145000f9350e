"""Dispatch layer over the entangled kernels — pluggable at the bottom.

Every public wrapper here handles, uniformly:

  * arbitrary trailing shapes (flattened to the sample axis) and padding to
    block multiples (zero padding is exact for integer LSB ops);
  * **backend dispatch through a registry** — each backend provides the
    three entangled LSB ops (``entangled_matmul``, ``entangled_conv1d``,
    ``entangled_matmul_grouped``) behind one calling convention; shipped
    backends are

      - ``pallas_tpu``     the compiled Pallas TPU kernels,
      - ``interpret_cpu``  the same kernels under ``interpret=True`` (the
                           task-mandated CPU validation mode; default off
                           TPU),
      - ``reference``      the pure-jnp oracles from :mod:`ref` (XLA
                           compiles them; no Pallas at all),

    and :func:`register_backend` accepts ports (see *Porting to
    Triton/CUDA* below). Selection order per call: explicit ``backend=``
    kwarg > legacy ``interpret=`` flag > process default
    (:func:`set_default_backend`, else platform: ``pallas_tpu`` on TPU,
    ``interpret_cpu`` elsewhere);
  * block-size dispatch via the ``blocks`` argument:
      - ``None``: shape-aware defaults (power-of-two, capped at the
        MXU/VPU-aligned 128/512 tiles);
      - a dict: explicit override, merged over the defaults;
      - ``"auto"``: the :mod:`repro.kernels.autotune` subsystem — sweep
        once per (op, shape, backend, flags) key, then cache-hit. Keys are
        **backend-namespaced** (the registry name is the key's backend
        field), so a registered port autotunes into its own namespace and
        the shipped pre-tuned seed caches (``kernels/pretuned/<name>.json``)
        can never leak winners across backends;
  * codec fusion via ``fuse_epilogue`` on the LSB-op wrappers: ``True``
    returns extracted true outputs from ONE fused kernel call (entangle ->
    op -> extract, zero intermediate HBM round-trips); ``False`` returns
    entangled outputs for callers that inject failures / persist entangled
    state, to be recovered later with :func:`disentangle`.

Porting to Triton/CUDA
----------------------
A port registers an impls dict mapping the three op names to callables with
the padded-call convention (see :data:`REQUIRED_OPS` and the builtin
registrations at the bottom of this module)::

    ops.register_backend("triton_cuda", {
        "entangled_matmul": my_triton_emm,          # (c, g, *, plan,
        "entangled_conv1d": my_triton_conv,         #  fuse_epilogue,
        "entangled_matmul_grouped": my_triton_emmg, #  failed, blocks,
    }, interpret=False)                             #  packed)

Each callable receives block-multiple-padded integer operands (GEMM
activations keep an int8/int16 dtype, which bounds them; everything else
is int32; a GEMM's output columns N are left unpadded, so its last column
block may be partial) and the resolved ``blocks`` dict and must reproduce
the reference oracle bit-exactly (``tests/test_fused_codec.py``
parametrizes over registered backends' semantics; the codec is
shifts/adds, so any backend that accumulates in int32 matches). :func:`triton_cuda_stub` returns a
placeholder impls dict whose entries raise ``NotImplementedError`` with
these porting notes — register it to reserve the namespace before the
kernels exist. Pre-tuned block sizes ship per backend as
``kernels/pretuned/<backend>.json``.

The per-kernel legacy block kwargs (``bb=/bn=/bk=``, ``bd=/bt=``,
``block_n=``) remain accepted and act as defaults under ``blocks``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.plan import EntanglePlan
from repro.kernels import autotune as at
from repro.kernels import codec
from repro.kernels import ref
from repro.kernels.codec import PACK_LANES
from repro.kernels.checksum import checksum_pallas
from repro.kernels.conv1d import conv1d_causal_pallas
from repro.kernels.disentangle import disentangle_pallas
from repro.kernels.entangle import entangle_pallas
from repro.kernels.entangled_conv1d import entangled_conv1d_pallas
from repro.kernels.entangled_matmul import entangled_matmul_pallas
from repro.kernels.entangled_matmul_grouped import (
    entangled_matmul_grouped_pallas)

Blocks = Union[None, str, dict]

# the op surface every backend must implement (padded-call convention)
REQUIRED_OPS = ("entangled_matmul", "entangled_conv1d",
                "entangled_matmul_grouped")


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """One registered kernel backend.

    ``impls`` maps each :data:`REQUIRED_OPS` name to a callable taking the
    block-multiple-padded int32 operands plus ``plan`` / ``fuse_epilogue``
    / ``failed`` / ``blocks`` keywords. ``interpret`` is the Pallas
    interpret flag used for the standalone codec passes (entangle /
    disentangle / checksum) that backends do not override.
    """

    name: str
    impls: Mapping[str, Callable]
    interpret: bool = True
    description: str = ""


_BACKENDS: dict[str, KernelBackend] = {}
_DEFAULT: Optional[str] = None  # set_default_backend override


def register_backend(name: str, impls: Mapping[str, Callable], *,
                     interpret: bool = True,
                     description: str = "") -> KernelBackend:
    """Register (or replace) a kernel backend under ``name``.

    ``impls`` must cover every op in :data:`REQUIRED_OPS`. Autotune keys
    for the backend are namespaced by ``name`` — a port never shares (or
    clobbers) another backend's winners, and a pre-tuned seed cache
    shipped as ``kernels/pretuned/<name>.json`` is picked up automatically.
    """
    missing = [op for op in REQUIRED_OPS if op not in impls]
    if missing:
        raise ValueError(
            f"backend {name!r} is missing required ops {missing}; every "
            f"backend must provide {list(REQUIRED_OPS)}")
    b = KernelBackend(name=name, impls=dict(impls), interpret=interpret,
                      description=description)
    _BACKENDS[name] = b
    return b


def unregister_backend(name: str) -> None:
    """Remove a registered backend (and the default pin, if it was it)."""
    global _DEFAULT
    _BACKENDS.pop(name, None)
    if _DEFAULT == name:
        _DEFAULT = None


def backend_names() -> tuple:
    return tuple(sorted(_BACKENDS))


def get_backend(name: str) -> KernelBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"no kernel backend {name!r} registered; known: "
            f"{backend_names()}") from None


def set_default_backend(name: Optional[str]) -> None:
    """Pin the process-wide default backend (None restores the platform
    rule: ``pallas_tpu`` on TPU, ``interpret_cpu`` elsewhere)."""
    global _DEFAULT
    if name is not None:
        get_backend(name)  # validate
    _DEFAULT = name


def resolve_backend(backend: Optional[str] = None,
                    interpret=None) -> str:
    """Resolve a wrapper call's backend name.

    Precedence: explicit ``backend`` kwarg > legacy ``interpret`` flag
    (True -> ``interpret_cpu``, False -> ``pallas_tpu``) > process default
    > platform rule. The returned name is also the autotune/pretuned cache
    namespace for the call.
    """
    if backend is not None:
        get_backend(backend)
        return backend
    if interpret is True:
        return "interpret_cpu"
    if interpret is False:
        return "pallas_tpu"
    if _DEFAULT is not None:
        return _DEFAULT
    return "pallas_tpu" if jax.default_backend() == "tpu" else "interpret_cpu"


def _gemm_operand(c: jax.Array) -> jax.Array:
    """GEMM activations keep an int8/int16 dtype — it bounds their values,
    and the Pallas kernels split them into that many int8 MXU limbs (one
    for int8); any other dtype widens to int32 (four limbs, exact for any
    value)."""
    return c if c.dtype in (jnp.int8, jnp.int16) else c.astype(jnp.int32)


def _columns_sharded(run: Callable, g_ndim: int, out_ndim: int):
    """``run(c, g)`` as one kernel per device when a multi-device
    :func:`repro.dist.sharding.axis_rules` mesh is active, else ``run``
    itself, plus the device count. A Pallas TPU kernel cannot be
    partitioned automatically, so under a mesh each device runs the kernel
    on every row (``c`` replicated) against its own slice of the weights'
    output columns (the last axis of ``g`` and of the output) — the codec
    is elementwise over columns, so extraction stays device-local and a
    stream's roll-forward needs nothing from another device."""
    from repro.dist.sharding import active_mesh  # deferred: no cycle

    mesh = active_mesh()
    if mesh is None:
        return run, 1
    cols = tuple(mesh.axis_names)
    spec = lambda nd: P(*([None] * (nd - 1)), cols)  # noqa: E731
    return jax.shard_map(run, mesh=mesh, in_specs=(P(), spec(g_ndim)),
                         out_specs=spec(out_ndim), check_vma=False), mesh.size


def _pad_to(x: jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def _resolve_blocks(op: str, defaults: dict, blocks: Blocks, shape_sig: tuple,
                    backend: str, bench, flags: tuple = ()) -> dict:
    """Merge/auto-tune the block sizes for one wrapper call."""
    if blocks is None:
        return defaults
    if isinstance(blocks, dict):
        return {**defaults, **blocks}
    if blocks == "auto":
        return at.tune(op, shape_sig, backend, bench, flags=flags)
    raise ValueError(f"blocks must be None, a dict or 'auto', got {blocks!r}")


# --------------------------------------------------------------- codec ------

def _plan_flags(plan: EntanglePlan) -> tuple:
    """Autotune key component for the codec parameters: the Horner depth
    and temp mode change the epilogue cost, so winners must not be shared
    across plans that merely agree on M and shapes."""
    return (f"l{plan.l}", plan.temp)


def _codec_pass(op: str, kernel_call, x: jax.Array, block_n: int,
                blocks: Blocks, backend: str, flags: tuple = ()):
    """Shared flatten -> pad -> resolve/tune -> kernel path for the
    elementwise [M, N] codec sweeps. ``kernel_call(padded, bn, interp)``
    invokes the kernel; returns (out, valid_n, original_shape)."""
    shape = x.shape
    flat = x.reshape(shape[0], -1).astype(jnp.int32)
    interp = get_backend(backend).interpret

    def bench(bl):
        padded, _ = _pad_to(flat, 1, bl["block_n"])
        return lambda: kernel_call(padded, bl["block_n"], interp)

    bl = _resolve_blocks(op, {"block_n": block_n}, blocks,
                         (shape[0], flat.shape[1]), backend, bench,
                         flags=flags)
    padded, n = _pad_to(flat, 1, bl["block_n"])
    return kernel_call(padded, bl["block_n"], interp), n, shape


def entangle(c: jax.Array, plan: EntanglePlan, *, block_n: int = 1024,
             blocks: Blocks = None, interpret=None,
             backend: Optional[str] = None) -> jax.Array:
    """Entangle M streams of any trailing shape ([M, ...] int)."""
    out, n, shape = _codec_pass(
        "entangle",
        lambda p, bn, it: entangle_pallas(p, l=plan.l, block_n=bn,
                                          interpret=it),
        c, block_n, blocks, resolve_backend(backend, interpret),
        flags=_plan_flags(plan))
    return out[:, :n].reshape(shape)


def disentangle(delta: jax.Array, plan: EntanglePlan, *,
                failed: Optional[int] = None, block_n: int = 1024,
                blocks: Blocks = None, interpret=None,
                backend: Optional[str] = None) -> jax.Array:
    """Recover all M outputs from entangled outputs of any trailing shape."""
    r = 0 if failed is None else failed
    out, n, shape = _codec_pass(
        "disentangle",
        lambda p, bn, it: disentangle_pallas(p, plan=plan, r=r, block_n=bn,
                                             interpret=it),
        delta, block_n, blocks, resolve_backend(backend, interpret),
        flags=_plan_flags(plan))
    return out[:, :n].reshape(shape)


def checksum(c: jax.Array, *, block_n: int = 1024, blocks: Blocks = None,
             interpret=None, backend: Optional[str] = None) -> jax.Array:
    """Checksum stream r = sum_m c_m for [M, ...] inputs -> [...]."""
    out, n, shape = _codec_pass(
        "checksum",
        lambda p, bn, it: checksum_pallas(p, block_n=bn, interpret=it),
        c, block_n, blocks, resolve_backend(backend, interpret))
    return out[0, :n].reshape(shape[1:])


# ------------------------------------------------------------- LSB ops ------

# valid fuse_epilogue values for the dense GEMM; grouped/conv accept only
# the first two (chaining is a dense-site feature — see ft/protected.py)
_FUSE_MODES = (False, True, "chain", "chain_final")


def _check_fuse(fuse_epilogue, *, chain_ok: bool) -> None:
    valid = _FUSE_MODES if chain_ok else _FUSE_MODES[:2]
    if fuse_epilogue not in valid:
        raise ValueError(
            f"fuse_epilogue must be one of {valid}, got {fuse_epilogue!r}")


def entangled_matmul(c: jax.Array, g: jax.Array, plan: EntanglePlan, *,
                     fuse_epilogue=False,
                     failed: Optional[int] = None,
                     bb: int = 128, bn: int = 128, bk: int = 128,
                     packed: bool = False,
                     blocks: Blocks = None, interpret=None,
                     backend: Optional[str] = None) -> jax.Array:
    """Fused entangle+GEMM[+extract]: c [M, B, K], g [K, N] int.

    ``fuse_epilogue=False`` -> entangled products [M, B, N] (recover later
    via :func:`disentangle`). ``fuse_epilogue=True`` -> true products, the
    codec never leaving the kernel; ``failed`` statically excludes one
    stream's accumulator from the in-kernel extraction. The chain modes
    ``'chain'`` / ``'chain_final'`` skip the entangle prologue — ``c`` must
    already be entangled (e.g. a previous call's ``fuse_epilogue=False``
    output) — and return entangled / extracted products respectively, so
    consecutive linear GEMMs compose without leaving the entangled domain.
    ``packed=True`` declares ``g`` as [ceil(K/4), N] int8 lanes packed 4
    per int32 word along K (:func:`repro.kernels.codec.pack_int8`); the
    kernels sign-extend-unpack in registers, so the weight sweep costs its
    true int8 bytes.
    """
    _check_fuse(fuse_epilogue, chain_ok=True)
    M, B, K = c.shape
    N = g.shape[1]
    c_op = _gemm_operand(c)
    g32 = g.astype(jnp.int32)
    bname = resolve_backend(backend, interpret)
    impl = get_backend(bname).impls["entangled_matmul"]
    r = 0 if failed is None else failed

    def call(bl, cc, gg):
        run, shards = _columns_sharded(
            lambda c, g: impl(c, g, plan=plan, fuse_epilogue=fuse_epilogue,
                              failed=r, blocks=bl, packed=packed), 2, 3)
        cp, _ = _pad_to(cc, 1, bl["bb"])
        cp, _ = _pad_to(cp, 2, bl["bk"])
        # packed weights pad along K in words (bk/4 words == bk lanes)
        gp, _ = _pad_to(gg, 0, bl["bk"] // PACK_LANES if packed else bl["bk"])
        # N is never padded to bn (the kernels take a partial last column
        # block), only to an even split over the devices
        gp, _ = _pad_to(gp, 1, shards)
        return run(cp, gp)

    bl = _resolve_blocks(
        "entangled_matmul", {"bb": bb, "bn": bn, "bk": bk}, blocks,
        (M, B, K, N), bname, lambda b: (lambda: call(b, c_op, g32)),
        flags=_matmul_flags(plan, fuse_epilogue, packed))
    out = call(bl, c_op, g32)
    return out[:, :B, :N]


def entangled_matmul_grouped(c: jax.Array, g: jax.Array, plan: EntanglePlan,
                             *, fuse_epilogue: bool = False,
                             failed: Optional[int] = None,
                             bb: int = 128, bn: int = 128, bk: int = 128,
                             packed: bool = False,
                             blocks: Blocks = None, interpret=None,
                             backend: Optional[str] = None) -> jax.Array:
    """Grouped fused entangle+GEMM[+extract] — the MoE per-expert form:
    c [M, E, Cg, K], g [E, K, N] int -> [M, E, Cg, N].

    Expert e's rows multiply expert e's weights; the codec spans the M
    stream axis only, so recovery semantics are identical to
    :func:`entangled_matmul` applied per expert (one kernel call covers
    all E). Ragged per-expert row counts must be padded to the uniform
    ``Cg`` by the caller with zero rows (exact — this is the same
    capacity-padding a bounded MoE dispatcher already performs).
    ``packed=True`` declares ``g`` as [E, ceil(K/4), N] int8 lanes packed
    along K. Chain modes are dense-only (raises here).
    """
    _check_fuse(fuse_epilogue, chain_ok=False)
    M, E, Cg, K = c.shape
    N = g.shape[2]
    c_op = _gemm_operand(c)
    g32 = g.astype(jnp.int32)
    bname = resolve_backend(backend, interpret)
    impl = get_backend(bname).impls["entangled_matmul_grouped"]
    r = 0 if failed is None else failed

    def call(bl, cc, gg):
        run, shards = _columns_sharded(
            lambda c, g: impl(c, g, plan=plan, fuse_epilogue=fuse_epilogue,
                              failed=r, blocks=bl, packed=packed), 3, 4)
        cp, _ = _pad_to(cc, 2, bl["bb"])
        cp, _ = _pad_to(cp, 3, bl["bk"])
        gp, _ = _pad_to(gg, 1, bl["bk"] // PACK_LANES if packed else bl["bk"])
        gp, _ = _pad_to(gp, 2, shards)  # N: see entangled_matmul
        return run(cp, gp)

    bl = _resolve_blocks(
        "entangled_matmul_grouped", {"bb": bb, "bn": bn, "bk": bk}, blocks,
        (M, E, Cg, K, N), bname, lambda b: (lambda: call(b, c_op, g32)),
        flags=_matmul_flags(plan, fuse_epilogue, packed))
    out = call(bl, c_op, g32)
    return out[:, :, :Cg, :N]


def _matmul_flags(plan: EntanglePlan, fuse_epilogue,
                  packed: bool = False) -> tuple:
    """Autotune flags for the fused GEMMs — single source of truth for the
    wrapper's tune call and the startup warm's cache lookup. Every
    fuse/packed variant gets its own namespace: the epilogue and the
    unpack prologue both change the kernel's cost profile, so winners must
    never be shared across them."""
    flags = _plan_flags(plan)
    if fuse_epilogue is True:
        flags += ("fused",)
    elif fuse_epilogue == "chain":
        flags += ("chain",)
    elif fuse_epilogue == "chain_final":
        flags += ("chainf",)
    if packed:
        flags += ("packed",)
    return flags


def _warm_acts_dtype(plan: EntanglePlan, K: int, fuse_epilogue):
    """Activation dtype of a protected GEMM with a ``K``-deep contraction:
    its eq.-13 grid's (int8 at published widths, one MXU limb); the chain
    modes carry entangled int32 accumulators (four limbs)."""
    from repro.ft.quantize import acts_dtype  # deferred: ft imports ops

    return jnp.int32 if fuse_epilogue in _FUSE_MODES[2:] else acts_dtype(
        plan, K)


def warm_entangled_matmul(M: int, B: int, K: int, N: int, plan: EntanglePlan,
                          *, fuse_epilogue=True, packed: bool = False,
                          interpret=None,
                          backend: Optional[str] = None) -> dict:
    """Eagerly autotune the fused GEMM for one (M, B, K, N) serving shape.

    The serving engine calls this at startup for every shape in its census:
    the sweep runs HERE, eagerly on real buffers, so that ``blocks="auto"``
    inside the engine's jitted decode step is a pure in-process cache hit
    (a sweep during tracing would time tracers, not kernels). ``failed`` is
    deliberately not part of the autotune key, so one warm covers healthy
    and every fail-stop-injected variant. The sweep's activations take the
    dtype the protected path quantizes to (:func:`_warm_acts_dtype`), so
    it times the limb count that serving runs. Returns the winning block
    sizes.
    """
    c = jnp.zeros((M, B, K), _warm_acts_dtype(plan, K, fuse_epilogue))
    Kg = -(-K // PACK_LANES) if packed else K
    g = jnp.zeros((Kg, N), jnp.int32)
    entangled_matmul(c, g, plan, fuse_epilogue=fuse_epilogue, packed=packed,
                     blocks="auto", interpret=interpret, backend=backend)
    key = at.cache_key("entangled_matmul", (M, B, K, N),
                       resolve_backend(backend, interpret),
                       _matmul_flags(plan, fuse_epilogue, packed))
    return at.get_cache().get(key) or {}


def warm_entangled_matmul_grouped(M: int, E: int, Cg: int, K: int, N: int,
                                  plan: EntanglePlan, *,
                                  fuse_epilogue: bool = True,
                                  packed: bool = False, interpret=None,
                                  backend: Optional[str] = None) -> dict:
    """Grouped twin of :func:`warm_entangled_matmul` for the MoE
    per-expert shapes of the engine census."""
    c = jnp.zeros((M, E, Cg, K), _warm_acts_dtype(plan, K, fuse_epilogue))
    Kg = -(-K // PACK_LANES) if packed else K
    g = jnp.zeros((E, Kg, N), jnp.int32)
    entangled_matmul_grouped(c, g, plan, fuse_epilogue=fuse_epilogue,
                             packed=packed, blocks="auto",
                             interpret=interpret, backend=backend)
    key = at.cache_key("entangled_matmul_grouped", (M, E, Cg, K, N),
                       resolve_backend(backend, interpret),
                       _matmul_flags(plan, fuse_epilogue, packed))
    return at.get_cache().get(key) or {}


def entangled_conv1d(x: jax.Array, w: jax.Array, plan: EntanglePlan, *,
                     fuse_epilogue: bool = False,
                     failed: Optional[int] = None,
                     bd: int = 128, bt: int = 512,
                     packed: bool = False,
                     blocks: Blocks = None, interpret=None,
                     backend: Optional[str] = None) -> jax.Array:
    """Fused entangle+depthwise-causal-conv[+extract]: x [M, B, D, T],
    w [D, K_f] int. Same fusion semantics as :func:`entangled_matmul`;
    ``packed=True`` declares ``w`` as [ceil(D/4), K_f] int8 lanes packed
    along the depth axis. Chain modes are dense-only (raises here)."""
    _check_fuse(fuse_epilogue, chain_ok=False)
    M, B, D, T = x.shape
    kf = w.shape[1]
    x32 = x.astype(jnp.int32)
    w32 = w.astype(jnp.int32)
    if kf == 1:  # kernel needs a halo; a zero leading tap is exact
        w32 = jnp.pad(w32, ((0, 0), (1, 0)))  # (zero packed word == 4
        kf = 2                                #  zero lanes, still exact)
    bname = resolve_backend(backend, interpret)
    impl = get_backend(bname).impls["entangled_conv1d"]
    r = 0 if failed is None else failed

    def call(bl, xx, ww):
        xp, _ = _pad_to(xx, 2, bl["bd"])
        xp, _ = _pad_to(xp, 3, bl["bt"])
        wp, _ = _pad_to(ww, 0, bl["bd"] // PACK_LANES if packed else bl["bd"])
        return impl(xp, wp, plan=plan, fuse_epilogue=fuse_epilogue,
                    failed=r, blocks=bl, packed=packed)

    bl = _resolve_blocks(
        "entangled_conv1d", {"bd": bd, "bt": bt}, blocks,
        (M, B, D, T, kf), bname, lambda b: (lambda: call(b, x32, w32)),
        flags=_plan_flags(plan) + (("fused",) if fuse_epilogue else ())
        + (("packed",) if packed else ()))
    out = call(bl, x32, w32)
    return out[:, :, :D, :T]


def conv1d_causal(x: jax.Array, w: jax.Array, *, bd: int = 128, bt: int = 512,
                  blocks: Blocks = None, interpret=None,
                  backend: Optional[str] = None) -> jax.Array:
    """Depthwise causal conv1d (unentangled): x [B, D, T], w [D, K_f]."""
    B, D, T = x.shape
    x32 = x.astype(jnp.int32)
    w32 = w.astype(jnp.int32)
    if w32.shape[1] == 1:  # kernel's halo slice needs K_f >= 2; a zero
        w32 = jnp.pad(w32, ((0, 0), (1, 0)))  # leading tap is exact
    bname = resolve_backend(backend, interpret)
    interp = get_backend(bname).interpret

    def call(bl, xx, ww):
        xp, _ = _pad_to(xx, 1, bl["bd"])
        xp, _ = _pad_to(xp, 2, bl["bt"])
        wp, _ = _pad_to(ww, 0, bl["bd"])
        return conv1d_causal_pallas(
            xp, wp, bd=bl["bd"], bt=bl["bt"], interpret=interp)

    bl = _resolve_blocks(
        "conv1d", {"bd": bd, "bt": bt}, blocks,
        (B, D, T, w.shape[1]), bname, lambda b: (lambda: call(b, x32, w32)))
    out = call(bl, x32, w32)
    return out[:, :D, :T]


# --------------------------------------------------- builtin backends -------

def _pallas_impls(interpret: bool) -> dict:
    return {
        "entangled_matmul": lambda c, g, *, plan, fuse_epilogue, failed,
        blocks, packed=False: entangled_matmul_pallas(
            c, g, plan=plan, fuse_epilogue=fuse_epilogue, failed=failed,
            bb=blocks["bb"], bn=blocks["bn"], bk=blocks["bk"],
            packed=packed, interpret=interpret),
        "entangled_matmul_grouped": lambda c, g, *, plan, fuse_epilogue,
        failed, blocks, packed=False: entangled_matmul_grouped_pallas(
            c, g, plan=plan, fuse_epilogue=fuse_epilogue, failed=failed,
            bb=blocks["bb"], bn=blocks["bn"], bk=blocks["bk"],
            packed=packed, interpret=interpret),
        "entangled_conv1d": lambda x, w, *, plan, fuse_epilogue, failed,
        blocks, packed=False: entangled_conv1d_pallas(
            x, w, plan=plan, fuse_epilogue=fuse_epilogue, failed=failed,
            bd=blocks["bd"], bt=blocks["bt"], packed=packed,
            interpret=interpret),
    }


def _ref_impls() -> dict:
    """The jnp oracles as a backend: semantics without any Pallas schedule
    (XLA lowers them directly; ``blocks`` is accepted and ignored). Packed
    weights are unpacked up front — the oracle defines semantics, not a
    memory schedule — and the chain modes compose the oracle pieces: a
    plain per-stream GEMM on the already-entangled input (linearity:
    ``(E c) @ g = E (c @ g)``), extracting only in ``'chain_final'``."""
    def emm(c, g, *, plan, fuse_epilogue, failed, blocks, packed=False):
        c = c.astype(jnp.int32)
        if packed:
            g = codec.unpack_int8(g, axis=0)
        if fuse_epilogue in ("chain", "chain_final"):
            out = jnp.stack([jnp.dot(c[m], g,
                                     preferred_element_type=jnp.int32)
                             for m in range(plan.M)], axis=0)
            if fuse_epilogue == "chain_final":
                out = codec.disentangle_block(out, plan, failed)
            return out
        if fuse_epilogue:
            return ref.entangled_matmul_fused_ref(c, g, plan, r=failed)
        return ref.entangled_matmul_ref(c, g, plan.l)

    def emmg(c, g, *, plan, fuse_epilogue, failed, blocks, packed=False):
        c = c.astype(jnp.int32)
        if packed:
            g = codec.unpack_int8(g, axis=1)
        if fuse_epilogue:
            return ref.entangled_matmul_grouped_fused_ref(c, g, plan,
                                                          r=failed)
        return ref.entangled_matmul_grouped_ref(c, g, plan.l)

    def econv(x, w, *, plan, fuse_epilogue, failed, blocks, packed=False):
        if packed:
            w = codec.unpack_int8(w, axis=0)
        if fuse_epilogue:
            return ref.entangled_conv1d_fused_ref(x, w, plan, r=failed)
        return ref.entangled_conv1d_ref(x, w, plan.l)

    return {"entangled_matmul": emm, "entangled_matmul_grouped": emmg,
            "entangled_conv1d": econv}


def triton_cuda_stub() -> dict:
    """Placeholder impls dict for the planned Triton/CUDA port.

    Registering it (``ops.register_backend("triton_cuda",
    ops.triton_cuda_stub(), interpret=False)``) reserves the backend
    namespace; calling any op raises with the porting contract. The real
    port replaces each entry with a Triton kernel implementing the same
    entangle-on-load / int32-accumulate / extract-at-flush schedule (see
    the module docstring and ``kernels/entangled_matmul.py``).
    """
    def _todo(op):
        def impl(*a, **k):
            raise NotImplementedError(
                f"triton_cuda backend: {op} is not ported yet. Implement "
                f"the fused schedule (entangle-on-load, int32 VMEM/SMEM "
                f"accumulate, disentangle at the k-flush) and validate "
                f"bit-exactly against repro.kernels.ref — then "
                f"ops.register_backend('triton_cuda', {{...}}) the real "
                f"impls and ship kernels/pretuned/triton_cuda.json")
        return impl

    return {op: _todo(op) for op in REQUIRED_OPS}


register_backend(
    "pallas_tpu", _pallas_impls(interpret=False), interpret=False,
    description="compiled Pallas TPU kernels (MXU int GEMM, fused codec)")
register_backend(
    "interpret_cpu", _pallas_impls(interpret=True), interpret=True,
    description="Pallas interpret mode — CPU validation of the exact "
                "kernel schedules")
register_backend(
    "reference", _ref_impls(), interpret=True,
    description="pure-jnp oracles (XLA-lowered; exactness baseline)")
