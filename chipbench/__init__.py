"""The benchmark of burst-attn-tpu: one command runs one cell once on the chip.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a later PR adds is a file found by its name in BENCHMARK.json:
`configs/<config>.json`, `traffic/<traffic>.json`, `layer_metrics/<metric>.py`,
`runners/<kind>.py`, `references/<name>.py`.  The yardstick (peaks, FLOP
arithmetic, trace reduction, references, the `correct` comparison) lives
here and reads nothing of the program but its entry points and kernel names.
"""
