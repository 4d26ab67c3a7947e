"""The chip benchmark of the learned oversubscription manager.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
