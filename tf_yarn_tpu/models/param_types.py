"""The type a served model holds each parameter in.

A checkpoint hands every parameter over in the type training kept it in
(float32 masters), while a model that computes in bfloat16 reads most of
them as `param.astype(cfg.dtype)`: the step then streams four bytes to
use two, every token, and rounds the same matrix to the same bfloat16
values every time. Rounding it once gives those values to every later
step, so a leaf whose every read is such a convert can be held in the
narrow type at no change of result (`x.astype(t).astype(t)` is
`x.astype(t)`), at half the bytes held and streamed.

Which leaves those are is read off the model, not listed: `narrow_types`
traces the served forward pass (`decode_engine.build_prefill_fn`, the
form every served program applies the model in) on abstract variables
and follows each parameter through the jaxpr. A leaf qualifies when
every equation that reads it — looking through the `jit`, `remat`,
`scan` and custom-derivative calls that pass it on whole — is a
`convert_element_type` to one and the same narrower float type. A leaf
read in its own type anywhere (a norm scale multiplied in float32, a
router, a recurrent decay), returned, or handed to an equation this walk
does not know stays as restored, which is what happened to every leaf
before. A model whose matrices are stored narrow already offers nothing.
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Equations that hand operand i, whole, to invar i of the jaxpr under the
# named parameter (a scanned operand arrives a slice at a time, in its own
# type). A call this table lacks makes its operands unclassifiable.
_CALLS = {
    "jit": "jaxpr", "closed_call": "call_jaxpr", "remat2": "jaxpr",
    "scan": "jaxpr", "custom_jvp_call": "call_jaxpr",
    "custom_vjp_call": "call_jaxpr",
}
_OTHER = object()  # a read that is not a convert


def _reads(jaxpr, var, found: set) -> None:
    """Add to `found` the type of every convert that reads `var` in
    `jaxpr`, and `_OTHER` for any other use of it."""
    if any(out is var for out in jaxpr.outvars):
        found.add(_OTHER)
    for eqn in jaxpr.eqns:
        for i, operand in enumerate(eqn.invars):
            if operand is not var:
                continue
            if eqn.primitive.name == "convert_element_type":
                found.add(np.dtype(eqn.params["new_dtype"]))
                continue
            inner = eqn.params.get(_CALLS.get(eqn.primitive.name))
            inner = getattr(inner, "jaxpr", inner)  # ClosedJaxpr -> Jaxpr
            if inner is not None and len(inner.invars) == len(eqn.invars):
                _reads(inner, inner.invars[i], found)
            else:
                found.add(_OTHER)


def narrow_types(model, variables) -> List[Optional[np.dtype]]:
    """For each leaf of `variables` (flatten order): the narrower float
    type the served forward pass converts it to at every read, or None
    where the leaf has to stay as it is."""
    from tf_yarn_tpu.models.decode_engine import build_prefill_fn

    leaves, treedef = jax.tree_util.tree_flatten(variables)
    abstract = treedef.unflatten(
        [jax.ShapeDtypeStruct(leaf.shape, leaf.dtype) for leaf in leaves])
    closed = jax.make_jaxpr(build_prefill_fn(model))(
        abstract, jax.ShapeDtypeStruct((1, 8), jnp.int32))
    types: List[Optional[np.dtype]] = []
    # make_jaxpr flattens its arguments in order: the variables' leaves
    # are the first invars, the prompt the last.
    for leaf, var in zip(leaves, closed.jaxpr.invars):
        found: set = set()
        _reads(closed.jaxpr, var, found)
        to = next(iter(found)) if len(found) == 1 else _OTHER
        narrower = (to is not _OTHER and _is_float(leaf.dtype)
                    and _is_float(to)
                    and to.itemsize < np.dtype(leaf.dtype).itemsize)
        types.append(to if narrower else None)
    return types


def _is_float(dtype) -> bool:
    return jnp.issubdtype(dtype, jnp.floating)


_convert = jax.jit(lambda x, to: x.astype(to), static_argnums=1)


def narrow(model, variables) -> Tuple[Any, int, int, int]:
    """`variables` with every leaf `narrow_types` names converted, once:
    (tree, leaves converted, bytes before, bytes after). The tree handed
    in is used up: each wide device array is deleted as soon as its
    narrow twin exists, so the peak is the wide tree plus one leaf. A
    converted leaf keeps its sharding; a host (numpy) leaf is converted
    on the default device, where the engine would have put it."""
    leaves, treedef = jax.tree_util.tree_flatten(variables)
    types = narrow_types(model, variables)
    before = sum(leaf.nbytes for leaf in leaves)
    for i, to in enumerate(types):
        if to is None:
            continue
        wide = leaves[i]
        # Elementwise, so the output takes the input's sharding.
        leaves[i] = _convert(wide, to)
        if isinstance(wide, jax.Array):
            leaves[i].block_until_ready()
            wide.delete()
    after = sum(leaf.nbytes for leaf in leaves)
    converted = sum(to is not None for to in types)
    return treedef.unflatten(leaves), converted, before, after

