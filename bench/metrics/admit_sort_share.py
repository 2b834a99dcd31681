"""Share of device busy time in queue admission's stable argsort
(``repro.core.fabric._group_admit``), in %.

The step has two kinds of HLO ``sort``: admission's ``jnp.argsort(...,
stable=True)``, whose HLO text says ``is_stable=true``, and the unstable
sorts of ``jnp.searchsorted`` in phase compaction (``_compact_idx``), over
a key and a boolean. Only the first counts here (checked by hand on a
v5e trace, see ``bench/tests/data``). Nothing to read where no stable sort
ran (the Pallas admission backend has none)."""


def is_admission_sort(name: str) -> bool:
    return " sort(" in name and "is_stable=true" in name


def read(ctx):
    if ctx.trace is None:
        return None
    sort_s = sum(t for n, t in ctx.trace["op_s"].items()
                 if is_admission_sort(n))
    if sort_s <= 0:
        return None
    return 100.0 * sort_s / ctx.trace["busy_s"]
