"""README.md names only what the tree holds: the files it points a
reader at exist, and the commands it tells a reader to run name a
module or a script that exists. A deleted harness or record cannot
stay in the README as if it were there."""
from __future__ import annotations

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _readme() -> str:
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
        return f.read()


def _ignored_dirs():
    """Directories `.gitignore` lists: what running leaves behind is
    named in the README and is not in the tree."""
    with open(os.path.join(ROOT, ".gitignore"), encoding="utf-8") as f:
        return tuple(ln.strip() for ln in f if ln.strip().endswith("/"))


def _missing_paths(text: str):
    """Backticked repository paths that do not exist. A path has a `/`
    (from the root, or from the package as the README writes
    `serve/disagg.py`) or is a bare name, which sits at the root or is
    a module's file name somewhere under the package."""
    package_files = {f for _, _, fs in os.walk(os.path.join(ROOT, "ray_tpu"))
                     for f in fs}
    missing = []
    for tok in sorted(set(re.findall(r"`([^`\n]+)`", text))):
        if not re.search(r"\.(py|json|md|sh)$", tok):
            continue
        if re.search(r"[\s<>*{}$]", tok) or tok[0] in "@/":
            continue  # a command, a placeholder or a pattern
        if tok.startswith(_ignored_dirs()):
            continue
        if "/" in tok:
            found = any(os.path.exists(os.path.join(ROOT, base, tok))
                        for base in ("", "ray_tpu"))
        else:
            found = (os.path.exists(os.path.join(ROOT, tok))
                     or tok in package_files)
        if not found:
            missing.append(tok)
    return missing


def _missing_commands(text: str):
    """`python -m ray_tpu.<module>` and `python3 <file>` commands whose
    module or file does not exist."""
    missing = []
    for mod in sorted(set(re.findall(r"python3? -m (ray_tpu[\w.]*)", text))):
        base = os.path.join(ROOT, *mod.rstrip(".").split("."))
        if not (os.path.exists(base + ".py")
                or os.path.exists(os.path.join(base, "__main__.py"))):
            missing.append(f"python -m {mod}")
    for path in sorted(set(re.findall(r"python3? ([\w./-]+\.py)", text))):
        if not os.path.exists(os.path.join(ROOT, path)):
            missing.append(f"python {path}")
    return missing


@pytest.mark.parametrize("find", [_missing_paths, _missing_commands],
                         ids=["paths", "commands"])
def test_readme_names_only_what_exists(find):
    assert find(_readme()) == []
