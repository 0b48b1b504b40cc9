"""Benchmark of the multi-tenant ETHER serving and finetuning system (see BENCHMARK.json)."""
