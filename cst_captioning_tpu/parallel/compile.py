"""Unified compile layer: one seam where step/decode programs meet jit.

Every jitted program in the repo used to spell its own compilation —
``jax.jit(fn)`` here, ``jax.jit(shard_map(fn, ...))`` there — which meant
the flagship-XL dp x mp refactor would have touched a dozen call sites with
conflicting axis bookkeeping. This module centralizes the choice behind a
:class:`CompilePlan` (the Titanax/SNIPPETS [3] idiom: a plan object picks
jit / shard_map / pjit, the factories just describe their specs):

- ``mesh=None``                      -> plain ``jax.jit`` (single device);
- ``mesh`` + ``in_specs``/``out_specs`` -> ``jax.jit(shard_map(fn, ...))``
  (the explicit-collectives spelling every factory uses today);
- ``how="pjit"``                     -> ``jax.jit`` with NamedSharding
  in/out shardings derived from the same specs (compiler-inserted
  collectives — the escape hatch for programs whose collectives are not
  hand-spelled, e.g. the mp=1 parameter-sharded eval path).

The emitted composition for the first two modes is byte-for-byte the
spelling the factories used before this layer existed, so the default
(mp=1) path stays bit-identical by construction — pinned in
tests/test_mp.py. dp x mp composes with ``parallel/submesh.py``'s
actor/learner split because both sides hand their (sub)mesh through the
same plan: a submesh of a 2-D ('data', 'mp') mesh is itself a 2-D mesh,
and the factories never inspect axis counts.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_MODES = ("auto", "jit", "shard_map", "pjit")


class CompileError(ValueError):
    """A CompilePlan that cannot be compiled as requested (missing mesh,
    one-sided specs, unknown mode) — raised at factory-build time, never
    from inside a traced program."""


@dataclass(frozen=True)
class CompilePlan:
    """How to compile one program.

    ``mesh``           — target mesh, or None for single-device jit.
    ``in_specs``       — PartitionSpec pytree for the inputs (shard_map /
                         pjit modes; None with ``mesh=None``).
    ``out_specs``      — PartitionSpec pytree for the outputs.
    ``donate_argnums`` — forwarded to ``jax.jit`` unchanged.
    ``how``            — "auto" (jit without a mesh, shard_map with one),
                         or an explicit "jit" / "shard_map" / "pjit".
    """

    mesh: Mesh | None = None
    in_specs: Any = None
    out_specs: Any = None
    donate_argnums: tuple[int, ...] = ()
    how: str = "auto"

    def __post_init__(self):
        if self.how not in _MODES:
            raise CompileError(
                f"unknown compile mode {self.how!r} (expected one of "
                f"{_MODES})"
            )
        if (self.in_specs is None) != (self.out_specs is None):
            raise CompileError(
                "CompilePlan needs BOTH in_specs and out_specs (or "
                "neither): one-sided specs silently replicate the other "
                "side"
            )

    def resolve(self) -> str:
        """The concrete mode "auto" lands on, with plan validation."""
        how = self.how
        if how == "auto":
            how = "jit" if self.mesh is None else "shard_map"
        if how == "jit":
            if self.in_specs is not None:
                raise CompileError(
                    "mode 'jit' ignores partition specs — drop them or "
                    "pick shard_map/pjit"
                )
            return how
        if self.mesh is None:
            raise CompileError(f"mode {how!r} needs a mesh")
        if self.in_specs is None:
            raise CompileError(
                f"mode {how!r} needs in_specs and out_specs"
            )
        return how


def partition(fn: Callable, plan: CompilePlan) -> Callable:
    """The shard_map half only — for factories whose ``jax.jit`` sits at a
    different level than the mesh program (the seq-parallel factories take
    grads OUTSIDE their shard_map)."""
    how = plan.resolve()
    if how != "shard_map":
        raise CompileError(
            f"partition() only builds shard_map programs, plan resolved to "
            f"{how!r}"
        )
    return jax.shard_map(
        fn, mesh=plan.mesh, in_specs=plan.in_specs, out_specs=plan.out_specs
    )


def _shardings(mesh: Mesh, specs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, PartitionSpec),
    )


def compile_fn(fn: Callable, plan: CompilePlan) -> Callable:
    """Compile ``fn`` per ``plan`` — the single seam all step/update
    factories, the evaluator, and CaptionService compile through."""
    how = plan.resolve()
    if how == "jit":
        return jax.jit(fn, donate_argnums=plan.donate_argnums)
    if how == "shard_map":
        return jax.jit(
            partition(fn, plan), donate_argnums=plan.donate_argnums
        )
    # pjit: same jit, compiler-inserted collectives from the sharding trees
    return jax.jit(
        fn,
        in_shardings=_shardings(plan.mesh, plan.in_specs),
        out_shardings=_shardings(plan.mesh, plan.out_specs),
        donate_argnums=plan.donate_argnums,
    )
