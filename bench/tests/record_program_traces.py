"""Records the trace fixtures of ``test_program_trace.py`` on a TPU.

    python3 bench/tests/record_program_traces.py [--out bench/tests/data]

Both traces have the program's spans on (``repro.serve.obs.enable()``)
and a ``bench.window`` span around the traced work:

* ``flat_program_20k.xplane.pb`` — flat DotVByte (f16 values) over
  20,000 documents of the ``msmarco-splade.flat-dotvbyte`` corpus,
  three searches of 32 queries through ``Retriever.search``, each
  result fetched in a ``bench.fetch`` span;
* ``seismic_program_20k.xplane.pb`` — the ``msmarco-splade.seismic-
  dotvbyte`` configuration (20,000 documents), ten requests through a
  ``Pipeline`` with buckets {1, 8}: one served alone at its deadline,
  eight that fill bucket 8 and dispatch at once, one more at its
  deadline.

Each plan is compiled and run once before the window. Python calls
are not traced, and the ``/host:metadata`` plane (the executables' HLO
protos, which nothing here reads) is dropped, so that each file stays
under 500 KB.
"""

from __future__ import annotations

import argparse
import glob
import os
import pathlib
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
for _p in (REPO / "src", REPO):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import corpus, run, spec  # noqa: E402
from bench.program_trace import _fields, _text  # noqa: E402
from bench.tests.harness import benchmark  # noqa: E402

SEED = 20_000
N_DOCS = 20_000
DROP_PLANES = ("/host:metadata",)


def strip_planes(buf: bytes, names=DROP_PLANES) -> bytes:
    """An ``XSpace`` without the planes named in ``names`` (every
    top-level field of an ``XSpace`` is length-delimited)."""
    view = memoryview(buf)
    out, i = bytearray(), 0
    for f, (_, end) in _fields(view):
        if f != 1 or not any(pf == 2 and _text(view, pv) in names
                             for pf, pv in _fields(view, _, end)):
            out += view[i:end]
        i = end
    return bytes(out)


def traced(work, out_path: pathlib.Path) -> None:
    """Run ``work(span)`` inside a ``bench.window`` span under the
    profiler and write the stripped trace to ``out_path``."""
    import jax

    span = jax.profiler.TraceAnnotation
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host spans only, not every Python call
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=options)
        with span("bench.window"):
            work(span)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True))[-1]
        out_path.write_bytes(strip_planes(pathlib.Path(path).read_bytes()))
    print(f"{out_path}: {out_path.stat().st_size} B", flush=True)


def cell_index(name: str, n_queries: int, n_docs: int):
    """The cell's configuration at ``n_docs`` documents, its dense
    queries and the program's index."""
    config = spec.resolve(benchmark(), name).config
    config["corpus"]["n_docs"] = n_docs
    corp = corpus.generate(run.profile(config, n_queries, SEED))
    return config, corp.queries_dense(), run.build(config, corp)


def record_flat(out: pathlib.Path, n_docs: int = N_DOCS) -> None:
    _, Q, r = cell_index("splade-flat.batch32", 96, n_docs)
    for b in range(2):
        np.asarray(r.search(Q[:32])[0])

    def work(span):
        for b in range(3):
            with span("bench.search"):
                ids, scores = r.search(Q[b * 32:(b + 1) * 32])
            with span("bench.fetch"):
                np.asarray(ids), np.asarray(scores)

    traced(work, out / "flat_program_20k.xplane.pb")


def record_seismic(out: pathlib.Path, n_docs: int = N_DOCS) -> None:
    config, Q, r = cell_index("splade-seismic.open", 10, n_docs)
    p = config["pipeline"]
    pipe = r.pipeline(buckets=p["buckets"], deadline_us=p["deadline_us"],
                      cache_size=p["cache_size"])
    for b in pipe.plans.buckets:
        np.asarray(pipe.plans.get(b)(np.zeros((b, Q.shape[1]), np.float32))[0])
    deadline_s = pipe.deadline_us * 1e-6

    def lone(span, q):
        with span("bench.submit"):
            t = pipe.submit(q)
        time.sleep(deadline_s)
        with span("bench.poll"):
            pipe.poll()
        assert t.done

    def work(span):
        lone(span, Q[0])
        for q in Q[1:9]:
            with span("bench.submit"):
                pipe.submit(q)
        lone(span, Q[9])

    traced(work, out / "seismic_program_20k.xplane.pb")
    assert dict(pipe.stats.dispatches) == {1: 2, 8: 1}, pipe.stats.dispatches


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(REPO / "bench" / "tests" / "data"))
    args = ap.parse_args(argv)
    from repro.serve import obs

    run.device_info(1)
    obs.enable()
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    record_flat(out)
    record_seismic(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
