"""A second family made of files alone, for ``test_families.py``: the
``gpt`` family's functions, each wrapped so that it counts its calls.
No file of ``benchmarks/lib``, ``run.py`` or ``tools`` names it; the
fixture configurations ``tally_train`` and ``tally_serve`` do."""
import collections

from benchmarks.lib.spec import FAMILY, load_family

_gpt = load_family("gpt")
CALLS = collections.Counter()


def _tallied(name):
    real = getattr(_gpt, name)

    def call(*args, **kw):
        CALLS[name] += 1
        return real(*args, **kw)

    return call


class _TalliedTable(dict):
    """Counts a look at the table: with no TPU plane on the trace a
    rehearsal's kernel reader gets as far as asking whether the name is
    there."""

    def __contains__(self, key):
        CALLS["KERNEL_WORK"] += 1
        return super().__contains__(key)


KERNEL_WORK = _TalliedTable(_gpt.KERNEL_WORK)
globals().update({name: _tallied(name) for name in FAMILY
                  if name != "KERNEL_WORK"})
