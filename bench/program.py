"""The one place that knows the system under test: how a configuration file
becomes a ``repro`` model, and its seeded weights in that model's parameter
tree, both through the layout the file names (``layouts/<kind>.py``).

The tree a layout builds is checked leaf by leaf against the tree the
program's own ``init_lm`` would make (shapes and dtypes), so a change of the
program's layout fails loudly here instead of serving other numbers.
"""
from __future__ import annotations

import jax

from common import layout_of, program_on_path

program_on_path()

from repro.models import build_lm, init_lm  # noqa: E402


def build(c: dict):
    return build_lm(layout_of(c).model_config(c))


def _check_tree(mine, lm) -> None:
    want = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), lm))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       mine)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError("the program's parameter tree changed:\n"
                         f"{jax.tree.structure(want)}\nvs\n"
                         f"{jax.tree.structure(got)}")
    bad = [(w, g) for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got))
           if (w.shape, w.dtype) != (g.shape, g.dtype)]
    if bad:
        raise ValueError(f"parameter leaves differ from the program's: {bad}")


def params(c: dict, lm, seed: int):
    """The seeded weights of the model ``lm`` in the program's tree, as the
    configuration's layout lays them out."""
    p = layout_of(c).params(c, lm, seed)
    _check_tree(p, lm)
    return p
