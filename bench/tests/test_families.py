"""A configuration names its model family: the harness finds the module
under the run's root, hands the program the family's workload, and stops
with the configuration file's name where the family is missing or
unknown.  The reference parts import nothing of the program."""
import json
import subprocess
import sys

import pytest

from bench import run
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    tiny.write_root(root)
    return root


@pytest.mark.parametrize("cell, family", sorted(tiny.CELLS.items()))
def test_the_program_runs_the_configured_family(root, cell, family):
    cell = run.Cell.load(cell, root)
    window, warm = run.build_specs(cell, 2 ** 31 + 5)
    assert {s.testbed.workload for s in window + warm} == {family}


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_a_missing_or_unknown_family_names_the_config_file(tmp_path, family):
    tiny.write_root(tmp_path)
    path = tmp_path / "bench" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    if family is None:
        del cfg["family"]
    else:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))
    with pytest.raises(SystemExit, match=r"configs/tiny\.json"):
        run.Cell.load("tiny", tmp_path)


def test_the_reference_and_families_import_nothing_of_the_program(root):
    code = f"""
import sys
from pathlib import Path
sys.path.insert(0, {str(tiny.REPO)!r})
from bench import reference, run
for r in (Path({str(tiny.REPO)!r}), Path({str(root)!r})):
    for p in sorted((r / "bench" / "families").glob("*.py")):
        run.load_family(p, {{"family": p.stem}}, r)
print(sorted(m for m in sys.modules if m.split(".")[0] == "repro"))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
