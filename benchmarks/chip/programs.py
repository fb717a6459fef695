"""The HLO of the programs a call runs, for classing traced ops.

``capture(jax)`` wraps ``jax.jit`` for the length of a ``with`` block,
so that every function the program jits inside it records the arguments
of its calls.  ``Capture.hlo_texts()`` then lowers and compiles each
recorded call again, which the persistent cache or the jit's own cache
serves, and returns the optimised HLO text of the very executables that
ran: the op names in the profiler's trace are instruction names of
these modules.  The traced run wraps its warm-up call only; the window
runs with ``jax.jit`` as it is.
"""

from __future__ import annotations

import contextlib


class _Recorded:
    """A jitted function that notes its calls while capture is on."""

    def __init__(self, jitted, capture):
        self._jitted = jitted
        self._capture = capture

    def __call__(self, *args, **kwargs):
        if self._capture.active:
            self._capture.calls.append((self._jitted, args, kwargs))
        return self._jitted(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


class Capture:
    def __init__(self):
        self.calls: list = []
        self.active = False

    def hlo_texts(self) -> dict[str, str]:
        """Module name -> optimised HLO text of every recorded call."""
        out = {}
        for jitted, args, kwargs in self.calls:
            text = jitted.lower(*args, **kwargs).compile().as_text()
            first = text.split("\n", 1)[0].split()
            name = first[1].rstrip(",") if len(first) > 1 else "?"
            out[name] = text
        self.calls.clear()
        return out


@contextlib.contextmanager
def capture(jax):
    cap = Capture()
    real = jax.jit

    def jit(fun, *args, **kwargs):
        return _Recorded(real(fun, *args, **kwargs), cap)

    jax.jit = jit
    cap.active = True
    try:
        yield cap
    finally:
        cap.active = False
        jax.jit = real
