"""Checking-service daemon entry point::

    python -m stateright_tpu.serve [HOST:PORT]
        [--journal PATH] [--journal-max-mb MB] [--knob-cache DIR]
        [--workers N] [--store-dir DIR] [--fleet-dir DIR]

``--journal-max-mb`` size-caps the journal into rotated segments
(``journal.jsonl.1..N``, runtime/journal.py) so a long-lived daemon
cannot grow one unbounded file; readers (``report``, read_journal)
merge segments transparently.  ``--store-dir`` enables the persistent
verification store for jobs submitted with ``store: true``
(docs/INCREMENTAL.md): identical resubmissions short-circuit to the
journaled verdict, near-identical ones take the cheapest sound
re-check path.

``--workers N`` (N ≥ 1) sizes the in-process scheduler pool.  These
workers are THREADS sharing the one accelerator mesh this process
owns — more of them overlaps host-side work (spec validation, journal
writes, knob-cache lookups) around serialized device runs; it does not
multiply device throughput.  For workers that each own a backend, use
fleet mode instead: ``--fleet-dir DIR`` makes this server a thin front
over the durable fleet store at DIR, with jobs run by separately
launched ``fleet-worker`` processes — one per CPU container, GPU box,
or TPU mesh (fleet/, docs/SERVING.md "Fleet mode").  ``--fleet-dir``
replaces the in-process backend, so it cannot be combined with
``--workers``, ``--journal``, ``--knob-cache``, or ``--store-dir``
(the fleet store has its own journal; knob caches belong to the worker
processes).

Serves until interrupted.  docs/SERVING.md documents the endpoints,
the job lifecycle, and the journal layout.
"""

from __future__ import annotations

import sys

DEFAULT_ADDRESS = "localhost:3100"


def main(argv=None) -> int:
    from ..runtime.compile_cache import use_compile_cache

    use_compile_cache()
    args = list(sys.argv[1:] if argv is None else argv)
    if args and args[0] in ("-h", "--help", "help"):
        print(__doc__.strip())
        return 0
    address = DEFAULT_ADDRESS
    journal = None
    journal_max_mb = None
    knob_cache = None
    store_dir = None
    fleet_dir = None
    workers = None
    positional = []
    i = 0
    while i < len(args):
        a = args[i]
        if a == "--journal":
            i += 1
            if i >= len(args):
                print("--journal requires a path", file=sys.stderr)
                return 2
            journal = args[i]
        elif a == "--journal-max-mb":
            i += 1
            try:
                journal_max_mb = float(args[i])
            except (IndexError, ValueError):
                print("--journal-max-mb requires a number of MB",
                      file=sys.stderr)
                return 2
            if journal_max_mb <= 0:
                print("--journal-max-mb must be positive", file=sys.stderr)
                return 2
        elif a == "--knob-cache":
            i += 1
            if i >= len(args):
                print("--knob-cache requires a directory", file=sys.stderr)
                return 2
            knob_cache = args[i]
        elif a == "--store-dir":
            i += 1
            if i >= len(args):
                print("--store-dir requires a directory", file=sys.stderr)
                return 2
            store_dir = args[i]
        elif a == "--fleet-dir":
            i += 1
            if i >= len(args):
                print("--fleet-dir requires a directory", file=sys.stderr)
                return 2
            fleet_dir = args[i]
        elif a == "--workers":
            i += 1
            try:
                workers = int(args[i])
            except (IndexError, ValueError):
                print("--workers requires an integer", file=sys.stderr)
                return 2
            if workers < 1:
                # A pool of zero threads would accept jobs that can
                # never run; refuse at the CLI boundary, loudly.
                print(
                    f"--workers must be >= 1, got {workers} (in-process "
                    "workers are threads sharing this process's one "
                    "mesh; for per-backend workers use --fleet-dir and "
                    "fleet-worker processes)",
                    file=sys.stderr,
                )
                return 2
        else:
            positional.append(a)
        i += 1
    if positional:
        address = positional[0]
    host, _, port = address.partition(":")
    try:
        port = int(port or DEFAULT_ADDRESS.rpartition(":")[2])
    except ValueError:
        print(f"invalid ADDRESS port: {address!r}", file=sys.stderr)
        return 2

    from .server import serve
    from .workloads import workload_names

    if fleet_dir is not None:
        incompatible = [
            flag for flag, val in (
                ("--workers", workers), ("--journal", journal),
                ("--journal-max-mb", journal_max_mb),
                ("--knob-cache", knob_cache), ("--store-dir", store_dir),
            ) if val is not None
        ]
        if incompatible:
            print(
                "--fleet-dir replaces the in-process backend and cannot "
                "be combined with " + ", ".join(incompatible) +
                " (the fleet store journals itself; knob caches and "
                "worker counts belong to the fleet-worker processes)",
                file=sys.stderr,
            )
            return 2
        print(
            f"Checking service on http://{host}:{port} "
            f"(fleet mode, store: {fleet_dir}, workloads: "
            f"{', '.join(workload_names())})",
            flush=True,
        )
        serve((host, port), block=True, fleet_dir=fleet_dir)
        return 0
    workers = 1 if workers is None else workers

    if journal_max_mb is not None:
        if journal is None:
            # Silently journaling nothing is the opposite of what the
            # size cap asks for; fail loudly at the CLI boundary.
            print(
                "--journal-max-mb requires --journal PATH (it size-caps "
                "that journal into rotated segments)",
                file=sys.stderr,
            )
            return 2
        from ..runtime.journal import Journal

        journal = Journal(
            journal, max_bytes=int(journal_max_mb * 1024 * 1024)
        )

    print(
        f"Checking service on http://{host}:{port} "
        f"(workers={workers}, workloads: {', '.join(workload_names())})",
        flush=True,
    )
    serve(
        (host, port), block=True, journal=journal,
        knob_cache_dir=knob_cache, workers=workers, store_dir=store_dir,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
