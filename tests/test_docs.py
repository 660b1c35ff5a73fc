"""Documentation stays executable (fast tier, every CI push).

Two checks keep the new docs surface from rotting:

* doctests on the public API (`engine/api.py`, `engine/store.py`,
  `engine/engine.py`, the phase-1 chunk rule in `engine/sharded.py`, the
  trainer surface `core/hat.py` + `launch/steps.py`, the multi-tenant
  surface `engine/tenant.py`, and the memory hierarchy `engine/router.py`
  + `engine/pager.py`) -- the same modules CI also runs through
  `pytest --doctest-modules`;
* extract-and-run over every ```python block in README.md and docs/*.md
  (blocks in one file share a namespace, so a later block may build on an
  earlier one; shell examples use ```bash fences and are not executed).
"""

import doctest
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

PUBLIC_MODULES = ("repro.engine.api", "repro.engine.store",
                  "repro.engine.engine", "repro.engine.tenant",
                  "repro.engine.router", "repro.engine.pager",
                  "repro.engine.sharded", "repro.core.hat",
                  "repro.launch.steps")


@pytest.mark.parametrize("modname", PUBLIC_MODULES)
def test_public_api_doctests(modname):
    """Every docstring example on the public surface runs and passes --
    and each of these modules is required to HAVE at least one (the
    docstring-pass contract of ISSUE 4)."""
    mod = __import__(modname, fromlist=["_"])
    res = doctest.testmod(mod, verbose=False)
    assert res.attempted > 0, f"{modname} lost its docstring examples"
    assert res.failed == 0, f"{modname}: {res.failed} doctest(s) failed"


DOC_FILES = ("README.md", "docs/architecture.md", "docs/migration.md")


@pytest.mark.parametrize("relpath", DOC_FILES)
def test_doc_exists_and_python_blocks_execute(relpath):
    """The documented code is real code: each ```python block compiles and
    executes (sequentially, sharing one namespace per file)."""
    path = ROOT / relpath
    assert path.exists(), f"{relpath} is part of the documented surface"
    blocks = re.findall(r"```python\n(.*?)```", path.read_text(), re.DOTALL)
    if relpath != "docs/architecture.md":   # architecture may be prose-only
        assert blocks, f"{relpath} has no ```python blocks"
    ns = {}
    for i, block in enumerate(blocks):
        code = compile(block, f"{relpath}[python block {i}]", "exec")
        exec(code, ns)                      # noqa: S102 -- the whole point
