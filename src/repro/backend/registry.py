"""Kernel-backend registry keyed on ``(platform, kernel, monoid, dtype)``.

Every kernel call in the repo is constructed through :func:`make_kernels` /
:func:`resolve`: the engine asks for a kernel by name (``gather`` /
``scatter`` / ``spmv`` / ``fold`` / ``fused_dc``) together with its monoid
and dtype, and
the registry hands back the implementation that is actually lowerable on
the current platform — ``ref`` (pure jnp), ``pallas-interpret`` (Pallas
bodies under the interpreter, any host), or ``pallas-native`` (Mosaic,
TPU only).  Selection order:

  1. an explicit ``backend=`` argument (``Engine(..., backend=...)``),
  2. the ``REPRO_KERNEL_BACKEND`` environment variable,
  3. the platform default: :data:`TPU_DEFAULTS` on TPU (each kernel's
     lowering named with the reason wherever it is not Mosaic, and
     :data:`TPU_REF_MONOIDS` for monoids no Mosaic kernel folds), ``ref``
     elsewhere.

If the selected backend cannot lower a particular ``(kernel, monoid,
dtype)`` combination (e.g. a ``min_with_payload`` uint64 fold asked of
Pallas, or any ``pallas-native`` call on a CPU host), that *call* falls
back to ``ref``
instead of failing — the rest of the engine keeps its chosen backend.
The fallback warns whenever the backend was asked for, and always on a
TPU: there a silent ``ref`` would hide that the declared table did not
hold.

Kernel ``fold`` (the shard_map-side blocked segmented fold,
:mod:`repro.kernels.fold_block` below ``REPRO_FOLD_MAX_SEGMENTS``
segments, the two-level :mod:`repro.kernels.fold_two_level` above it) is
the one kernel whose *platform default* is Pallas everywhere:
``pallas-native`` on TPU and ``pallas-interpret`` on other hosts, so the
distributed gather runs the paper's blocked VMEM fold at every segment
count — never ``jax.ops`` scatter-adds — unless
``REPRO_KERNEL_BACKEND=ref`` explicitly opts out.

Kernel ``fused_dc`` (the fused scatter→fold DC step,
:mod:`repro.kernels.fused_step`) is selection-special the other way:
:func:`make_kernels` constructs it only when the *selected* backend
itself lowers the ``(monoid, dtype)`` combination — no per-call ``ref``
fallback — because the engines' fallback for a missing fused kernel is
their own composed scatter→fold path, not a different backend.
``REPRO_FUSED=0`` opts the engines out of selecting it at all.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from ..kernels import ops as kops

ENV_VAR = "REPRO_KERNEL_BACKEND"
KERNELS = ("gather", "scatter", "spmv", "fold", "fused_dc")
PALLAS_MONOIDS = ("add", "min", "max")
#: kernels whose Pallas bodies Mosaic compiles (``fused_dc`` gathers in
#: XLA and folds with the two-level kernel)
MOSAIC_KERNELS = ("gather", "scatter", "spmv", "fold", "fused_dc")

#: The TPU table: each kernel's default lowering on a TPU, and why, where
#: it is not the Mosaic kernel.  ``chip_smoke.py`` prints it and fails
#: when a kernel ran on anything else.  The composed DC kernels build a
#: ``[tile, q]`` one-hot per edge tile, which at scale 22 with k = 32
#: (q = 131072) is out of reach; they stay selectable by name.
TPU_DEFAULTS = {
    "gather": ("ref", "its [tile, q] one-hot needs 131.75 MiB of the 128 "
               "MiB VMEM at q = 131072"),
    "scatter": ("ref", "its one-hot select gather makes q = 131072 compares "
                "per message and takes ~170 s to compile there"),
    "spmv": ("ref", "its one-hot select gather makes q = 131072 compares "
             "per edge and takes ~140 s to compile there"),
    "fold": ("pallas-native", None),
    "fused_dc": ("pallas-native", None),
}


#: Monoids the TPU table sends to ``ref`` for every kernel, and why: the
#: server's semantic cache and the parent-tracking apps fold this one.
TPU_REF_MONOIDS = {
    "min_with_payload": "its packed (key, payload) lattice is uint64, and "
                        "Mosaic has no 64-bit integer vectors",
}


def _monoid_obj(monoid):
    """Accept a Monoid or a monoid name (resolved at the default dtype)."""
    if isinstance(monoid, str):
        from ..core.monoid import REGISTRY
        return REGISTRY[monoid]()
    return monoid


@runtime_checkable
class KernelBackend(Protocol):
    """Factory for layout-bound kernels sharing the engine-facing API."""

    name: str

    def supports(self, platform: str, kernel: str, monoid: str,
                 dtype) -> bool: ...

    def gather(self, layout, monoid) -> Any: ...

    def scatter(self, layout, monoid) -> Any: ...

    def spmv(self, layout, weighted=None) -> Any: ...

    def segment_fold(self, monoid, tile=None, q=None) -> Any: ...

    def fused_dc(self, layout, monoid) -> Any: ...

    def fused_stream(self, monoid, tile=None, q=None) -> Any: ...


class RefBackend:
    """Pure-jnp backend: supports every monoid the Monoid type can fold."""

    name = "ref"

    def supports(self, platform, kernel, monoid, dtype):
        if kernel == "spmv":
            return monoid == "add" and jnp.issubdtype(jnp.dtype(dtype),
                                                      jnp.floating)
        return kernel in KERNELS

    def gather(self, layout, monoid):
        return kops.RefGather(layout, _monoid_obj(monoid))

    def scatter(self, layout, monoid):
        return kops.RefScatter(layout, _monoid_obj(monoid))

    def spmv(self, layout, weighted=None):
        return kops.RefSpmv(layout, weighted=weighted)

    def segment_fold(self, monoid, tile=None, q=None):
        return kops.RefFold(_monoid_obj(monoid))

    def fused_dc(self, layout, monoid):
        return kops.RefFusedDC(layout, _monoid_obj(monoid))

    def fused_stream(self, monoid, tile=None, q=None):
        return kops.RefFusedStream(_monoid_obj(monoid))


class PallasBackend:
    """Pallas kernel bodies, interpreted (any host) or Mosaic (TPU)."""

    def __init__(self, name: str, interpret: bool):
        self.name = name
        self.interpret = interpret

    def supports(self, platform, kernel, monoid, dtype):
        if not self.interpret and (platform != "tpu"
                                   or kernel not in MOSAIC_KERNELS):
            return False                     # Mosaic lowering is TPU-only
        dt = jnp.dtype(dtype)
        if kernel == "spmv":
            return monoid == "add" and dt == jnp.float32
        if kernel not in ("gather", "scatter", "fold", "fused_dc"):
            return False
        return monoid in PALLAS_MONOIDS and dt.kind in "fiu" \
            and dt.itemsize == 4

    def gather(self, layout, monoid):
        mono = _monoid_obj(monoid)
        return kops.GatherKernel(layout, mono.name, mono.dtype,
                                 interpret=self.interpret)

    def scatter(self, layout, monoid):
        mono = _monoid_obj(monoid)
        return kops.ScatterKernel(layout, mono.name, mono.dtype,
                                  interpret=self.interpret)

    def spmv(self, layout, weighted=None):
        return kops.SpmvKernel(layout, interpret=self.interpret,
                               weighted=weighted)

    def segment_fold(self, monoid, tile=None, q=None):
        mono = _monoid_obj(monoid)
        return kops.FoldKernel(mono.name, mono.dtype,
                               interpret=self.interpret, tile=tile, q=q)

    def fused_dc(self, layout, monoid):
        mono = _monoid_obj(monoid)
        return kops.FusedDCKernel(layout, mono.name, mono.dtype,
                                  interpret=self.interpret)

    def fused_stream(self, monoid, tile=None, q=None):
        mono = _monoid_obj(monoid)
        return kops.FusedStreamKernel(mono.name, mono.dtype,
                                      interpret=self.interpret,
                                      tile=tile, q=q)


BACKENDS: dict[str, KernelBackend] = {
    "ref": RefBackend(),
    "pallas-interpret": PallasBackend("pallas-interpret", interpret=True),
    "pallas-native": PallasBackend("pallas-native", interpret=False),
}


def available_backends() -> tuple[str, ...]:
    return tuple(BACKENDS)


def default_backend_name(platform: Optional[str] = None,
                         kernel: Optional[str] = None,
                         monoid: Optional[str] = None) -> str:
    """Platform default, after the ``REPRO_KERNEL_BACKEND`` override.

    The default is per-kernel: on TPU it is :data:`TPU_DEFAULTS`, or
    ``ref`` for a monoid in :data:`TPU_REF_MONOIDS`; off-TPU ``fold`` (no
    efficient ``jax.ops``-free lowering exists outside Pallas) defaults
    to the interpreted Pallas kernel, everything else to ``ref``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        if env not in BACKENDS:
            raise ValueError(
                f"{ENV_VAR}={env!r} is not a known backend; "
                f"choose one of {available_backends()}")
        return env
    platform = platform or jax.default_backend()
    if platform == "tpu":
        if monoid in TPU_REF_MONOIDS:
            return "ref"
        return TPU_DEFAULTS[kernel][0] if kernel else "pallas-native"
    return "pallas-interpret" if kernel == "fold" else "ref"


def supported(platform: str, kernel: str, monoid, dtype) -> tuple[str, ...]:
    """Registry view: backend names supporting (platform, kernel, monoid,
    dtype)."""
    mono = _monoid_obj(monoid)
    name = mono.name if not isinstance(monoid, str) else monoid
    return tuple(n for n, b in BACKENDS.items()
                 if b.supports(platform, kernel, name, dtype))


def resolve(kernel: str, monoid, dtype=None, platform: Optional[str] = None,
            choice=None) -> KernelBackend:
    """Pick the backend for one kernel call, with per-call ref fallback."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; expected {KERNELS}")
    mono = _monoid_obj(monoid)
    dtype = mono.dtype if dtype is None else dtype
    platform = platform or jax.default_backend()
    # a fallback warns when the backend was *asked for* (argument or env
    # override), and always on a TPU, where the declared table must hold
    explicit = (choice is not None or bool(os.environ.get(ENV_VAR))
                or platform == "tpu")
    if choice is None:
        name = default_backend_name(platform, kernel, mono.name)
        backend = BACKENDS[name]
    elif isinstance(choice, str):
        if choice not in BACKENDS:
            raise ValueError(f"unknown backend {choice!r}; "
                             f"choose one of {available_backends()}")
        backend = BACKENDS[choice]
    else:
        backend = choice                    # a KernelBackend instance
    if backend.supports(platform, kernel, mono.name, dtype):
        return backend
    ref = BACKENDS["ref"]
    if backend is not ref and ref.supports(platform, kernel, mono.name,
                                           dtype):
        if explicit:
            warnings.warn(
                f"backend {backend.name!r} does not lower kernel={kernel!r} "
                f"monoid={mono.name!r} dtype={jnp.dtype(dtype).name} on "
                f"platform={platform!r}; falling back to 'ref'",
                RuntimeWarning, stacklevel=2)
        return ref
    raise ValueError(
        f"no backend lowers kernel={kernel!r} monoid={mono.name!r} "
        f"dtype={jnp.dtype(dtype).name} on platform={platform!r}")


def _tag_scope(kernel, kname: str, backend_name: str):
    """Attach the ``jax.named_scope`` path the kernel's ``__call__`` enters
    (see :mod:`repro.obs.tracing`): profiler captures then attribute
    device time to ``ppm.<kernel>.<backend>`` regions.  The attribute is
    set on the kernel object itself — never a wrapper — so introspection
    like ``kset.fold.q`` keeps working."""
    try:
        kernel._obs_scope = f"ppm.{kname}.{backend_name}"
    except AttributeError:
        pass                               # e.g. a slotted/builtin callable
    return kernel


@dataclasses.dataclass
class KernelSet:
    """Layout-bound kernels for one engine, resolved per call."""

    gather: Any
    scatter: Any
    fold: Any
    spmv: Any
    names: dict                  # kernel -> backend name actually used
    fused: Any = None            # fused DC step, None -> composed path

    @property
    def any_pallas(self) -> bool:
        # the fold defaults to Pallas on every platform, so it says nothing
        # about whether the engine *chose* a Pallas backend
        return any(n.startswith("pallas") for k, n in self.names.items()
                   if k != "fold")


def make_kernels(layout, monoid, backend=None, platform=None,
                 with_spmv: bool = False) -> KernelSet:
    """Resolve and construct the gather/scatter/fold (and optionally spmv)
    kernels for a layout; each call may fall back to ``ref`` on its own."""
    mono = _monoid_obj(monoid)
    gb = resolve("gather", mono, platform=platform, choice=backend)
    sb = resolve("scatter", mono, platform=platform, choice=backend)
    fb = resolve("fold", mono, platform=platform, choice=backend)
    names = {"gather": gb.name, "scatter": sb.name, "fold": fb.name}
    spmv = None
    if with_spmv:
        vb = resolve("spmv", "add", dtype=jnp.float32, platform=platform,
                     choice=backend)
        spmv = _tag_scope(vb.spmv(layout), "spmv", vb.name)
        names["spmv"] = vb.name
    fold = fb.segment_fold(mono,
                           tile=getattr(layout, "fold_tile", None),
                           q=getattr(layout, "fold_q", None))
    # fused DC step: constructed only when the *selected* backend itself
    # lowers it — deliberately no per-call ref fallback here, because the
    # engines' fallback for a missing fused kernel is the composed
    # scatter→fold path (same backend), not a different backend
    fused = None
    platform_r = platform or jax.default_backend()
    if backend is None:
        xb = BACKENDS[default_backend_name(platform_r, "fused_dc",
                                           mono.name)]
    elif isinstance(backend, str):
        xb = BACKENDS[backend]
    else:
        xb = backend
    if xb.supports(platform_r, "fused_dc", mono.name, mono.dtype):
        fused = _tag_scope(xb.fused_dc(layout, mono), "fused_dc", xb.name)
        names["fused_dc"] = xb.name
    return KernelSet(gather=_tag_scope(gb.gather(layout, mono),
                                       "gather", gb.name),
                     scatter=_tag_scope(sb.scatter(layout, mono),
                                        "scatter", sb.name),
                     fold=_tag_scope(fold, "fold", fb.name),
                     spmv=spmv, names=names, fused=fused)
