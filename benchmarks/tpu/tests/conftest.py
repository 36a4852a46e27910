"""The benchmark's CPU tests build their tiny benchmark with
``tinycells_longctx.make_tiny_bench``, which knows every cell of
``BENCHMARK.json``, the latent-attention cell among them."""
import tinycells
import tinycells_longctx

tinycells.make_tiny_bench = tinycells_longctx.make_tiny_bench
