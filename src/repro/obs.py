"""Host spans of the system's layers, on the profiler's clock.

``span("fleet.pack_csr", packets=n)`` is a ``jax.profiler.TraceAnnotation``
named ``repro.fleet.pack_csr``.  While a profiler runs
(``jax.profiler.trace``) each span is an event of the host plane of the
trace, whose clock the device planes share, so the device's idle time
can be put down to the host phase that was running; with no profiler
running a span costs about a microsecond.  Counters ride a span as
keyword arguments, the event's stats.  A counter known only once the
work is done is set inside the ``with`` block::

    with obs.span("fleet.pack_csr") as sp:
        ...
        sp.set_metadata(packets=n)

Spans nest on the calling thread, and none sits inside a per-fragment,
per-key or per-epoch loop.

=========================  ==============================================
span                       what it covers (counters)
=========================  ==============================================
``system.init``            building a ``DiSketchSystem`` (``fragments``)
``replay.epoch_packet``    packing one epoch's switch streams, or a cache
                           hit (``hit``, ``packets``)
``system.run_window``      one control window; its self time is the
                           Eq. 6 control replay (``epoch0``, ``epochs``)
``fleet.run_window``       the fleet's window dispatch (``epochs``,
                           ``rows``)
``fleet.prepare``          masking, the mass check, the parameter table,
                           the folded packet flags
``fleet.select``           one subepoch group's segments of the window's
                           packets
``fleet.pack_csr``         one CSR packing (``packets``; ``slots``, the
                           packet slots of every block with the shape
                           bucket's padding; ``slots_live``, those of the
                           rows' own blocks)
``fleet.launch``           host side of an update or assembly launch:
                           tiling, transfers, enqueue (``h2d_bytes``)
``fleet.sync``             a blocking device-to-host read (the counter
                           peak, the PEB vector)
``fleet.records``          the window's per-epoch records and PEBs
``query.flows``            one ``query_flows`` call (``request``, the
                           system's sequence number; ``keys``; ``paths``;
                           ``device_calls``, its device launches;
                           ``batched_keys``, the keys one batched call
                           answered; ``fallback_paths``, the paths sent
                           one by one to the device)
``query.prep``             host work before a device query call: row
                           selection, liveness, routing, parameters, key
                           padding
``query.launch``           transfers and enqueue of one gather/merge
                           (``h2d_bytes``: host arrays only; ``keys``;
                           ``paths`` where it serves many)
``query.sync``             the blocking read of its estimates (of every
                           launch of a batched request at once)
=========================  ==============================================
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "repro."


def span(name: str, **counts: int) -> TraceAnnotation:
    """The host span ``repro.<name>``, with ``counts`` as its stats."""
    return TraceAnnotation(PREFIX + name, **counts)
