"""The benchmark's own library: everything that decides a number lives here,
under BENCHMARK.json's ``paths``, where a PR that claims a gain cannot edit it."""
