"""The benchmark of Checkmate on the chip: `BENCHMARK.json` names its cells;
`bench/run.py` runs one."""
