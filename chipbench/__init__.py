"""On-chip benchmark of the FedComLoc round (see ``BENCHMARK.json``).

Run one cell once with ``python3 chipbench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` from the root of a checkout.
"""
