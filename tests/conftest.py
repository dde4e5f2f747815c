"""Test-session hooks.

Under ``pytest -n N --dist loadfile`` each test module is one unit of work,
so the slowest module bounds the whole run's wall time. The modules named in
``_BY_PARAM`` hold independent cases, one per parameter (an architecture in
``test_arch_smoke.py``, about twenty CPU-minutes in all); their cases are
grouped by parameter instead, so that the groups spread over the workers.
Every other module stays one unit, and every test runs as before.

xdist forms its units through the scheduler's ``_split_scope``; the
scheduler below checks, once it has formed them, that its override was
the one used, and stops the run if a newer xdist has bypassed it.
"""

import pytest

_BY_PARAM = ("tests/test_arch_smoke.py",)


def split_scope(nodeid: str) -> str:
    """The unit of work of test ``nodeid``: its module, or for a module in
    ``_BY_PARAM`` its module and parameter."""
    path, _, name = nodeid.partition("::")
    if path in _BY_PARAM and name.endswith("]"):
        return f"{path}[{name.rsplit('[', 1)[1]}"
    return path


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class _ParamSplitScheduling(LoadFileScheduling):
        split_used = False

        def _split_scope(self, nodeid):
            self.split_used = True
            return split_scope(nodeid)

        def schedule(self):
            super().schedule()
            if self.collection and not self.split_used:
                raise RuntimeError(
                    "xdist formed its work units without "
                    "_ParamSplitScheduling._split_scope; tests/conftest.py "
                    "must follow the installed xdist's scheduler")

    return _ParamSplitScheduling(config, log)
