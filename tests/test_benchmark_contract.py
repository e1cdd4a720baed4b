"""Tier 1 holds the benchmark's contract: the pure-Python cases of
benchmark/tests/test_contract.py (no engine, no JAX, a second) run here
too, so that a world file, a module it names or a per-layer metric that
steps outside the seam fails the repo's own tests and not only the
harness's (`python3 -m pytest benchmark/tests`, outside tier 1).

The cases are the harness's, imported: BENCHMARK.json keeps to the
contract's shapes and every world file to the seam (each module it
names is there; of the four roles only the adapter reaches the
program); the first cell resolves to the defaults; a world file that
names what is not there is refused before anything is built; every
per-layer metric has a reader and every reader its metric; a verdict
takes a flavor and a quantity of several resources.
"""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "benchmark_test_contract", os.path.join(
        os.path.dirname(HERE), "benchmark", "tests", "test_contract.py"))
_contract = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_contract)

globals().update({name: case for name, case in vars(_contract).items()
                  if name.startswith("test_")})
