"""Stable names for the ops' work on the device.

Every public delivery, sampler and ring op, and every collective of a
node-sharded tick (``ops/mesh.py``), runs under a ``jax.named_scope`` named
``ops.<module>.<function>``.  A scope is HLO metadata (the ``op_name``
path of every operation traced inside it): nothing computed changes and it
costs nothing at run time, but a profiler trace can then say which op a
fusion belongs to, under a name that survives renumbering by the compiler.
Scopes nest (an op called from an engine phase sits under the phase's
scope); the innermost one owns an operation.

Each module keeps its names in a ``SCOPES`` tuple, built by the decorator, so
a test can enumerate them and a name cannot drift from its function.
"""

from __future__ import annotations

import functools
import inspect

import jax


def scoped(prefix: str, names: list):
    """Decorator factory: run ``fn`` under ``jax.named_scope(f"{prefix}.
    {fn.__name__}")`` and record the name in ``names``.  A generator is
    scoped while it runs, not while its consumer does: the scope is
    re-entered around each resumption."""

    def deco(fn):
        name = f"{prefix}.{fn.__name__}"
        names.append(name)
        if inspect.isgeneratorfunction(fn):

            @functools.wraps(fn)
            def gen(*args, **kwargs):
                it = fn(*args, **kwargs)
                while True:
                    with jax.named_scope(name):
                        try:
                            item = next(it)
                        except StopIteration:
                            return
                    yield item

            return gen

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapped

    return deco
