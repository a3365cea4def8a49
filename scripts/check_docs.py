"""Docs link-and-snippet check.

1. Executes every ```python code block in README.md and docs/*.md top to
   bottom (one shared namespace per file), so the quickstarts and the
   engine-guide walkthroughs keep running exactly as written. The blocks
   run in a child process (`--snippets`), so this parent never imports
   JAX: on a TPU host a parent holding the chip would starve every child.
2. Verifies that every repo path (src/..., benchmarks/..., examples/...,
   tests/..., docs/...) referenced in README.md and docs/*.md exists.
3. Verifies that every dotted `repro.*` module reference resolves to a
   real module file or package under src/.
4. Runs the executor quickstart `examples/jax_sweep.py` as a subprocess,
   so the README's backend walkthrough cannot rot.

Run from the repo root (CI does):  python scripts/check_docs.py
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
SRC = ROOT / "src"

PATH_RE = re.compile(
    r"\b(?:src|benchmarks|examples|tests|docs)/[A-Za-z0-9_\-./*]*[A-Za-z0-9_*]"
)
MODULE_RE = re.compile(r"\brepro(?:\.[a-z0-9_]+)+\b")
CODE_BLOCK_RE = re.compile(r"```python\n(.*?)```", re.DOTALL)


def doc_files() -> list[Path]:
    return [ROOT / "README.md", *sorted((ROOT / "docs").glob("*.md"))]


def check_paths() -> list[str]:
    errors = []
    for doc in doc_files():
        text = doc.read_text()
        for ref in sorted(set(PATH_RE.findall(text))):
            ref = ref.rstrip(".")
            if "*" in ref:
                if not any(ROOT.glob(ref)):
                    errors.append(f"{doc.name}: glob {ref!r} matches nothing")
            elif not (ROOT / ref).exists():
                errors.append(f"{doc.name}: missing path {ref!r}")
    return errors


def module_resolves(dotted: str) -> bool:
    """True if some prefix of `dotted` (>= 2 segments) is a module/package;
    trailing segments are assumed to be attributes of it."""
    parts = dotted.split(".")
    for end in range(len(parts), 1, -1):
        p = SRC.joinpath(*parts[:end])
        if p.with_suffix(".py").exists() or (p / "__init__.py").exists():
            return True
    return False


def check_modules() -> list[str]:
    errors = []
    for doc in doc_files():
        text = doc.read_text()
        for ref in sorted(set(MODULE_RE.findall(text))):
            if not module_resolves(ref):
                errors.append(f"{doc.name}: unresolvable module {ref!r}")
    return errors


def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def run_doc_snippets() -> list[str]:
    """Execute the doc snippets in a child process; its errors come back
    one per line on stdout, prefixed `ERROR: `."""
    print("-- executing doc snippets in a child process")
    try:
        proc = subprocess.run([sys.executable, str(Path(__file__).resolve()),
                               "--snippets"], env=_child_env(), cwd=ROOT,
                              capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return ["doc snippets timed out after 600s"]
    sys.stdout.write(proc.stdout)
    errors = [line[len("ERROR: "):] for line in proc.stdout.splitlines()
              if line.startswith("ERROR: ")]
    if proc.returncode != 0 and not errors:
        errors.append(f"snippet runner exited {proc.returncode}: "
                      f"{proc.stderr.strip()[-400:]}")
    return errors


def _exec_doc_snippets() -> list[str]:
    errors = []
    for doc in doc_files():
        text = doc.read_text()
        namespace: dict = {"__name__": f"__{doc.stem}__"}
        for i, block in enumerate(CODE_BLOCK_RE.findall(text), 1):
            print(f"-- executing {doc.name} python block {i} "
                  f"({len(block.splitlines())} lines)")
            try:
                exec(compile(block, f"{doc.name}:block{i}", "exec"), namespace)
            except Exception as e:  # noqa: BLE001 - report, don't crash
                errors.append(f"{doc.name} python block {i} failed: {e!r}")
    return errors


# example scripts doubling as executable documentation (README refers to
# them); each runs in a subprocess with src/ on the path
EXAMPLE_SCRIPTS = ("examples/jax_sweep.py",)


def run_example_scripts() -> list[str]:
    errors = []
    env = _child_env()
    for rel in EXAMPLE_SCRIPTS:
        print(f"-- running {rel}")
        try:
            proc = subprocess.run([sys.executable, str(ROOT / rel)],
                                  env=env, cwd=ROOT, capture_output=True,
                                  text=True, timeout=600)
        except subprocess.TimeoutExpired:
            errors.append(f"{rel} timed out after 600s")
            continue
        if proc.returncode != 0:
            errors.append(f"{rel} exited {proc.returncode}: "
                          f"{proc.stderr.strip()[-400:]}")
    return errors


def main() -> int:
    if sys.argv[1:] == ["--snippets"]:
        errors = _exec_doc_snippets()
        for e in errors:
            print(f"ERROR: {e!s}".replace("\n", " "))
        return 1 if errors else 0
    errors = check_paths() + check_modules()
    errors += run_doc_snippets()
    errors += run_example_scripts()
    if errors:
        print("\n".join(f"ERROR: {e}" for e in errors))
        return 1
    print("docs check OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
