#!/usr/bin/env python3
"""`tools/sweep.py` for a configuration of any driver.

    python3 perfbench/tools/sweep_any.py <config> <mix> <seed> <seconds> <rate> [<rate> ...]

`sweep.py` drives `drivers/serve_llama` by name; a PR that adds a cell may
not edit it. This runs its `main` with the configuration's own driver (which
gives the same `build`, `warm_up`, `lead_in`, `window`, `latency_numbers` and
`occupancy_between`) in that place. The next benchmark PR should let
`sweep.py` read `driver` from the configuration and delete this file.
"""

import importlib
import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)


def main():
    import sweep
    from perfbench import drivers
    from perfbench.harness import common
    cfg = common.load_json("configs", sys.argv[1] + ".json")
    driver = importlib.import_module("perfbench.drivers." + cfg["driver"])
    importlib.import_module("perfbench.drivers.serve_llama")
    with mock.patch.object(drivers, "serve_llama", driver):
        sweep.main()


if __name__ == "__main__":
    main()
