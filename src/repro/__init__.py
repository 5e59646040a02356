"""repro — Forward Index Compression for Learned Sparse Retrieval,
as a production-grade JAX/Pallas framework. See DESIGN.md."""

__version__ = "1.1.0"
