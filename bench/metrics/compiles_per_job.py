"""Programs compiled or loaded in the untraced window over the jobs answered in it.

Counted by a ``jax.monitoring`` listener on
``/jax/core/compile/backend_compile_duration``, which JAX records around
``compile_or_get_cached``: one event per program that missed JAX's
in-memory caches, whether the backend compiled it or it was loaded from
the persistent cache. The count repeats exactly.
"""


def read(m):
    return m.compiles / m.jobs if m.jobs else None
