"""Kinds added by later PRs, found by name and never by an edit.

``perfbench.plugins.gen_<kind>``     ``make(spec, n, rng, cols, np_dtype)``
``perfbench.plugins.refop_<op>``     ``apply(op, tables, lowprec)``
``perfbench.plugins.reader_<kind>``  ``read(spec, ctx)``
``perfbench.plugins.count_<name>``   ``count(config, traffic, rows)``

A kind that the built-in tables of ``datagen``, ``reference``, ``readers``
and ``counts`` lack is looked up here.
"""

import importlib


def find(group: str, kind: str, attr: str):
    try:
        mod = importlib.import_module(f"perfbench.plugins.{group}_{kind}")
    except ModuleNotFoundError as e:
        raise KeyError(
            f"perfbench: no {group} kind {kind!r} (built in or under "
            f"perfbench/plugins/{group}_{kind}.py)"
        ) from e
    return getattr(mod, attr)
