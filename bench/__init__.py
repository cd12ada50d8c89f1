"""Chip benchmark of the serving path: one cell (a model configuration
under a traffic mix) per run, driven as an open loop on the wall clock.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, cell or
metric is a file of its own under this directory, found by the name that
``BENCHMARK.json`` gives it:

    configs/<config>.json     sizes as run, source, cut, assumptions
    reference/<kind>.py       the plain float32 forward a config names
    traffic/<mix>.json        lengths, rate, slots, window rule
    cells/<cell>.json         the correctness limit and its readings
    metrics/<metric>.py       one reader per metric (``read(run)``)
    peaks.json                the one table of device peaks
    work/                     operation and byte counts
"""
