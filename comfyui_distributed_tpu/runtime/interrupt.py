"""Process-global interrupt flag.

The reference inherits ComfyUI's interrupt: ``common_ksampler`` checks a
processing flag between denoise steps (reference
``distributed_upscale.py:516-541`` runs under ComfyUI's executor, whose
``/interrupt`` route flips that flag).  Here the flag is checked wherever
the HOST regains control: between workflow nodes and tile batches
(``ops/base.py check_interrupt``) and at every step boundary of the
continuous-batching executor.  An ``lax.scan`` denoise loop is one
compiled program, so an interrupt that arrives while it runs takes effect
when it returns.

``DTPU_INTERRUPT_POLL=1`` compiles a per-step poll INTO the scan
(``models/samplers.py _scan_sampler``): a host callback reads the flag
each step and the scan skips the model call once it is set, returning the
partially-denoised latent within one step.  It is off by default because
JAX never writes a program that holds a host callback to the persistent
compile cache, on any backend: with the poll in, every new server
process compiles its denoise programs again (measured on the chip:
PERF.md, PR 21).

One process-global event mirrors ComfyUI's global processing-interrupted
semantics; the server's ``/interrupt`` route sets it, the executor clears it
at run start.
"""

from __future__ import annotations

import os
import threading

import numpy as np

_event = threading.Event()


def interrupt_event() -> threading.Event:
    """The process-wide interrupt event (shared with the server state)."""
    return _event


def request_interrupt() -> None:
    _event.set()


def clear_interrupt() -> None:
    _event.clear()


def is_interrupted() -> bool:
    return _event.is_set()


def polling_enabled() -> bool:
    """Whether compiled scan samplers poll the flag each step
    (``DTPU_INTERRUPT_POLL=1``; see the module docstring for its price).
    Part of the sample program's cache key (``registry.sample``)."""
    return os.environ.get("DTPU_INTERRUPT_POLL", "0") == "1"


def poll(_sequencer=None) -> np.bool_:
    """Host-callback body: reads the flag.  The ignored operand exists so
    callers can pass a carry-dependent scalar, giving the callback a data
    dependency on the previous step (otherwise XLA could hoist all the
    polls to the start of the scan)."""
    return np.bool_(_event.is_set())
