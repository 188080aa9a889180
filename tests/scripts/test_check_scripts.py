"""The check scripts name only what the tree holds: a PR that deletes a
script, a module or a test file must not leave a CI step pointing at it."""

import importlib.util
import re
import subprocess
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
SCRIPTS = sorted(p.name for p in (REPO / "scripts").glob("run_*.sh"))

# `python scripts/x.py`, and any scripts/ or tests/ file named anywhere in the text
PATHS = re.compile(r"(?:\bpython3?\s+|\b(?=scripts/|tests/))([\w./-]+\.(?:py|sh))\b")
# `python -m a.b` on a command line, `"-m", "a.b"` in an argument list of a heredoc
MODULES = re.compile(r"""(?:\bpython3?\s+-m\s+|["']-m["'],\s*["'])([A-Za-z_][\w.]*)""")


def _module_exists(module: str) -> bool:
    top = module.split(".")[0]
    if not ((REPO / top).is_dir() or (REPO / f"{top}.py").is_file()):
        return importlib.util.find_spec(top) is not None  # pytest and the like: installed, not ours
    stem = REPO.joinpath(*module.split("."))
    return stem.with_suffix(".py").is_file() or (stem / "__main__.py").is_file()


@pytest.mark.parametrize("name", SCRIPTS)
def test_check_script_parses_and_names_only_what_exists(name):
    script = REPO / "scripts" / name
    proc = subprocess.run(["bash", "-n", str(script)], capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    text = script.read_text()
    paths, modules = set(PATHS.findall(text)), set(MODULES.findall(text))
    assert paths or modules, f"{name} names nothing: the patterns above no longer read it"
    missing = [p for p in sorted(paths) if not (REPO / p).is_file()]
    missing += [f"-m {m}" for m in sorted(modules) if not _module_exists(m)]
    assert not missing, f"{name} names what the tree does not hold: {missing}"
