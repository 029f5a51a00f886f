"""Test-session bootstrap.

Installs a minimal in-process fallback for ``hypothesis`` when the real
package is unavailable (hermetic CI containers where ``pip install`` is
not an option).  The fallback implements exactly the strategy surface
this suite uses and draws deterministic pseudo-random examples — the
first example per strategy is the minimal/boundary draw, mirroring
hypothesis's shrink-toward-minimal bias.  With ``pip install -e .[test]``
the real hypothesis is present and this module does nothing.

It also provides the ``profiled`` fixture: a JAX profiler session and
the program's spans read back from its trace.
"""
from __future__ import annotations

import contextlib
import functools
import glob
import os
import random
import sys
import types
import warnings

import pytest

try:
    import hypothesis  # noqa: F401  (real package wins when installed)
except ImportError:
    _DEFAULT_MAX_EXAMPLES = 10

    class _Strategy:
        def __init__(self, minimal, draw):
            self._minimal = minimal
            self._draw = draw

        def example_from(self, rng, minimal=False):
            return self._minimal(rng) if minimal else self._draw(rng)

    def integers(min_value, max_value):
        return _Strategy(lambda rng: min_value,
                         lambda rng: rng.randint(min_value, max_value))

    def floats(min_value, max_value):
        return _Strategy(lambda rng: min_value,
                         lambda rng: rng.uniform(min_value, max_value))

    def booleans():
        return _Strategy(lambda rng: False, lambda rng: rng.random() < 0.5)

    def sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda rng: elements[0],
                         lambda rng: rng.choice(elements))

    def just(value):
        return _Strategy(lambda rng: value, lambda rng: value)

    def one_of(*strategies):
        return _Strategy(
            lambda rng: strategies[0].example_from(rng, minimal=True),
            lambda rng: rng.choice(strategies).example_from(rng))

    def lists(elements, min_size=0, max_size=10):
        def minimal(rng):
            return [elements.example_from(rng, minimal=True)
                    for _ in range(min_size)]

        def draw(rng):
            n = rng.randint(min_size, max_size)
            return [elements.example_from(rng) for _ in range(n)]

        return _Strategy(minimal, draw)

    def text(alphabet="abcdefghijklmnopqrstuvwxyz", min_size=0, max_size=20):
        chars = list(alphabet)
        return _Strategy(
            lambda rng: "".join(chars[0] for _ in range(min_size)),
            lambda rng: "".join(rng.choice(chars)
                                for _ in range(rng.randint(min_size,
                                                           max_size))))

    def given(*strategies):
        def decorate(fn):
            @functools.wraps(fn)
            def wrapper():
                n = getattr(wrapper, "_stub_max_examples",
                            _DEFAULT_MAX_EXAMPLES)
                rng = random.Random(fn.__qualname__)
                for i in range(n):
                    args = [s.example_from(rng, minimal=(i == 0))
                            for s in strategies]
                    fn(*args)
            # wraps() exposes fn's argful signature via __wrapped__, which
            # pytest would resolve as fixtures; the wrapper takes no args.
            del wrapper.__wrapped__
            return wrapper
        return decorate

    def settings(max_examples=_DEFAULT_MAX_EXAMPLES, **_ignored):
        def decorate(fn):
            fn._stub_max_examples = max_examples
            return fn
        return decorate

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = given
    _hyp.settings = settings
    _st = types.ModuleType("hypothesis.strategies")
    for _name, _fn in [("integers", integers), ("floats", floats),
                       ("booleans", booleans), ("sampled_from", sampled_from),
                       ("just", just), ("one_of", one_of), ("lists", lists),
                       ("text", text)]:
        setattr(_st, _name, _fn)
    _hyp.strategies = _st
    _hyp.__is_repro_stub__ = True
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st


class Profiled:
    """``with profiled.session(): ...`` collects a JAX profiler trace;
    ``profiled.spans(prefix)`` then reads back the host annotations whose
    names start with ``prefix`` as dicts (line, name, start, end, stats),
    in start order; spans of one thread share a ``line``."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    @contextlib.contextmanager
    def session(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(self.log_dir, profiler_options=opts):
            yield

    def spans(self, prefix: str):
        from jax.profiler import ProfileData
        path, = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        out = []
        # jaxlib's nanobind stats type warns when first built
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in ProfileData.from_file(path).planes:
                if not plane.name.startswith("/host:"):
                    continue
                # a line is a thread's; threads may share a line name
                for k, line in enumerate(plane.lines):
                    out.extend({"line": (plane.name, k), "name": e.name,
                                "start": e.start_ns,
                                "end": e.start_ns + e.duration_ns,
                                "stats": dict(e.stats)}
                               for e in line.events
                               if e.name.startswith(prefix))
        return sorted(out, key=lambda e: (e["start"], -e["end"]))


@pytest.fixture
def profiled(tmp_path):
    return Profiled(str(tmp_path / "profile"))
