"""Where the served weights sit on the device.

An array nobody gave a layout sits in the one the client picks for its
shape. A program compiled for that layout as a given, whose loops want a
weight in another, copies the whole weight there in its entry computation,
once a call: for ``falcon-7b``'s 4544-wide matrices that was 3.7 GiB
written and read again every decode chunk of 8 steps (PERF.md §6, PR 33).
Left free to choose (``Layout.AUTO`` on the parameters) the compiler names
the layouts it wants and reads every weight where it lies. So the engine
asks its decode program once, at load (``asked_formats``), places the
weights as it answers (``place``), and every program compiles against that
placement, because ``jax.jit`` compiles for the layout a committed
argument has.

One thing more is needed for the program that asked. Compiled for the very
layouts the free compile returned, but as GIVEN ones, a program with two
nested loops (decode: steps, layers) still carries ``falcon-7b``'s
``mlp.wo`` through them in another layout and copies it in ``main``; with
one loop level (every prefill) it reads it in place either way. So the
decode programs tell the compiler what the free compile chose: each
layer's slice of a stack is read in the layout the stack lies in
(``models/transformer.forward(weight_layouts=)``, ``stack_layouts`` here).

Nothing here names a model or tests a width: a tree whose layouts the
program already agrees with comes back as it went in.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, List, Optional, Sequence

import jax
from jax.experimental.layout import Format, Layout


@dataclasses.dataclass
class Placement:
    """What ``place`` did: leaves put into another layout and their
    bytes, and leaves whose asked-for layout could not be applied and
    that stay where they were (``why`` is the first such error)."""
    leaves_replaced: int = 0
    bytes_replaced: int = 0
    leaves_kept: int = 0
    why: str = ""


def _shape_of(a):
    return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                sharding=getattr(a, "sharding", None))


def asked_formats(fn: Callable, params, state, *args,
                  **jit_kwargs) -> List[Format]:
    """The format the compiled ``fn(params, state, *args)`` asks of each
    leaf of ``params`` (``jax.tree.leaves`` order) when the compiler is
    free to choose their layouts; the shardings stay the leaves' own.
    Lowered from shapes through a ``jax.jit`` that exists only for this
    question and is never called; ``jit_kwargs`` are the donation and
    output shardings the program really runs with."""
    free = jax.tree.map(lambda a: Format(Layout.AUTO, a.sharding), params)
    probe = jax.jit(fn, in_shardings=(free, None) + (None,) * len(args),
                    **jit_kwargs)
    shapes = jax.tree.map(_shape_of, (params, state, *args))
    return jax.tree.leaves(
        probe.lower(*shapes).compile().input_formats[0][0])


def same_layout(a: Layout, b: Layout) -> bool:
    """Whether two layouts name the same placement (a layout built by
    hand has no tiling where the backend reports an empty one)."""
    def key(lay):
        return (lay.major_to_minor, lay.tiling or (),
                getattr(lay, "_sub_byte_element_size_in_bits", 0))
    return key(a) == key(b)


@contextlib.contextmanager
def _fresh_compiles():
    """No persistent compilation cache inside: putting an array into a
    layout is a jitted identity whose RESULT has that layout, and on a TPU
    the executable the cache hands back for it gives the default layout
    instead (found on the chip, PERF.md §6, PR 33: the placement took in a
    process that compiled the identity and failed in every process that
    loaded it). The programs that only TAKE such layouts load from the
    cache as they were compiled."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def put_fresh(leaf, fmt: Format):
    """``jax.device_put(leaf, fmt)``, compiled and not loaded."""
    with _fresh_compiles():
        return jax.device_put(leaf, fmt)


def place(leaves: List[Any], wanted: Sequence[Format],
          put: Callable[[Any, Format], Any] = put_fresh) -> Placement:
    """Put every array of ``leaves`` whose layout differs from its entry
    of ``wanted`` into the asked-for one, IN the list and a leaf at a
    time: the list takes the copy and lets go of the source before the
    next leaf is touched, so where the list is the weights' only holder
    the transient is one leaf, not a second set of weights. A leaf that
    agrees is not touched; one whose layout cannot be applied is kept as
    it is and counted."""
    done = Placement()
    for i, fmt in enumerate(wanted):
        try:
            if same_layout(fmt.layout, leaves[i].format.layout):
                continue
            moved = put(leaves[i], fmt)
            if not same_layout(moved.format.layout, fmt.layout):
                raise ValueError(
                    f"asked for {fmt.layout}, the backend gave "
                    f"{moved.format.layout}")
        except Exception as exc:   # noqa: BLE001 - whatever the backend raises
            done.leaves_kept += 1
            done.why = done.why or f"{type(exc).__name__}: {exc}"
            continue
        done.leaves_replaced += 1
        done.bytes_replaced += moved.nbytes
        leaves[i] = moved
        del moved
    return done


def stack_layouts(leaves: Sequence[Any]) -> List[Optional[Layout]]:
    """For each weight (``jax.tree.leaves`` order of the parameters) the
    layout it has on the device, as ``forward(weight_layouts=)`` takes it;
    None where the backend names none."""
    return [getattr(getattr(leaf, "format", None), "layout", None)
            for leaf in leaves]
