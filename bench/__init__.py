"""The chip benchmark: data-driven cells over the serving engine (see BENCHMARK.json)."""
