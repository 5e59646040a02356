"""End-to-end serving driver (deliverable b): serve a small collection
with batched requests through the unified ``repro.serve.api`` surface.

Builds SPLADE + LILSR collections, constructs a Seismic index and an
HNSW graph over the same forward index, runs batched search with every
codec registered in ``core/layout.py`` — uncompressed, DotVByte,
StreamVByte and bitpack rows — and reports recall / per-query latency /
index bytes: the serving analogue of the paper's Table 2, plus the
graph-vs-inverted-index comparison of EXPERIMENTS.md §Graph.

Run:  PYTHONPATH=src python examples/retrieval_serving.py [--n-docs 8000]
(the HNSW host build is a few ms per doc; use --no-hnsw to skip it)
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hnsw import HNSWIndex, HNSWParams
from repro.core.layout import available_layouts
from repro.core.seismic import SeismicIndex, SeismicParams, exact_top_k, recall_at_k
from repro.data.synthetic import generate_collection, lilsr_config, splade_config
from repro.serve.api import Retriever, RetrieverConfig

CODECS = available_layouts()


def _serve(name, retriever, Q, truth, col, k):
    ids, _ = retriever.search(Q)  # warm-up / compile
    t0 = time.perf_counter()
    ids, _ = retriever.search(Q)
    np.asarray(ids)
    dt = (time.perf_counter() - t0) * 1e6 / Q.shape[0]
    rec = np.mean([recall_at_k(truth[i], np.asarray(ids[i]))
                   for i in range(Q.shape[0])])
    codec = retriever.cfg.codec
    comp = col.fwd.storage_bytes(codec)["components"]
    print(f"  {name:8s} {codec:13s} recall@{k}={rec:.3f} "
          f"{dt:8.0f} µs/query ({jax.devices()[0].device_kind})  "
          f"components={comp/2**20:6.2f} MiB")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-docs", type=int, default=6000)
    ap.add_argument("--n-queries", type=int, default=48)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--no-hnsw", action="store_true",
                    help="skip the graph-engine section (faster)")
    args = ap.parse_args()

    for enc, cfg_fn in (("splade", splade_config), ("lilsr", lilsr_config)):
        print(f"\n=== {enc}: {args.n_docs} docs ===")
        col = generate_collection(cfg_fn(args.n_docs, args.n_queries, seed=0),
                                  value_format="f16")
        index = SeismicIndex.build(col.fwd, SeismicParams(n_postings=1500, block_size=64))
        Q = jnp.asarray(np.stack([col.query_dense(i) for i in range(args.n_queries)]))
        truth = [exact_top_k(col.fwd, np.asarray(Q[i]), args.k)[0]
                 for i in range(args.n_queries)]

        for codec in CODECS:
            r = Retriever.from_host_index(
                index,
                RetrieverConfig(engine="seismic", codec=codec, k=args.k,
                                params=dict(cut=8, block_budget=512, n_probe=96)))
            _serve("seismic", r, Q, truth, col, args.k)

        if args.no_hnsw:
            continue
        graph = HNSWIndex.build(col.fwd, HNSWParams(m=16, ef_construction=48))
        for codec in CODECS:
            r = Retriever.from_host_index(
                graph,
                RetrieverConfig(engine="hnsw", codec=codec, k=args.k,
                                params=dict(beam=96, iters=96, n_seeds=8)))
            _serve("hnsw", r, Q, truth, col, args.k)


if __name__ == "__main__":
    main()
