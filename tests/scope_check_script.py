"""Subprocess harness: the op_name scopes of the superstep compiled over
a 4-device mesh (the main test process has one device).  Prints one
JSON line of {opcode: [op_name, ...]} for the instructions the test
looks at, then SCOPE_CHECK_OK."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.pop("JAX_PLATFORMS", None)

import json
import re
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import get_smoke
from repro.launch.train import Superstep

OPCODES = ("collective-permute", "while")


def main():
    run = Superstep(get_smoke("qwen2-0.5b"), jax.devices()[:4], agents=4,
                    walks=2, batch_per_agent=2, seq=16, place=False)
    hlo = run.lower(run.abstract_batch()).compile().as_text()
    found = {op: [] for op in OPCODES}
    for line in hlo.splitlines():
        for op in OPCODES:
            if re.search(rf" {op}(-start)?\(", line):
                name = re.search(r'op_name="([^"]*)"', line)
                found[op].append(name.group(1) if name else "")
    print(json.dumps(found))
    print("SCOPE_CHECK_OK")


if __name__ == "__main__":
    main()
