"""Every sub-command ``rt --help`` lists reaches code that exists.

``ray_tpu/scripts/cli.py`` imports what a sub-command runs only when it runs
(a lambda round ``__import__``, an import inside ``cmd_*``, a passthrough at
the top of ``main``), so a sub-command whose module has been deleted still
parses, still shows in the help, and fails only for the user who types it.
One case per sub-parser: the modules its handler imports are imported here.
"""

import argparse
import ast
import dis
import importlib
import inspect
import re
import types

import pytest

from ray_tpu.scripts import cli

_MODULE = re.compile(r"^ray_tpu(\.\w+)+$")


def _listed():
    """The names ``main`` gives to ``sub.add_parser``, read off the source so
    that collecting this file builds no parser."""
    tree = ast.parse(inspect.getsource(cli.main))
    return [c.args[0].value for c in ast.walk(tree)
            if isinstance(c, ast.Call) and isinstance(c.func, ast.Attribute)
            and c.func.attr == "add_parser"
            and isinstance(c.func.value, ast.Name) and c.func.value.id == "sub"]


SUBCOMMANDS = _listed()


@pytest.fixture(scope="module")
def parsers():
    """``name -> sub-parser`` of the parser ``main`` builds, caught at its
    ``parse_args`` (``main`` has no function that returns the parser)."""
    caught = {}

    def parse_args(self, args=None, namespace=None):
        caught["parser"] = self
        raise KeyboardInterrupt  # nothing in main() swallows it

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(argparse.ArgumentParser, "parse_args", parse_args)
        with pytest.raises(KeyboardInterrupt):
            cli.main(["status"])
    (action,) = [a for a in caught["parser"]._actions
                 if isinstance(a, argparse._SubParsersAction)]
    return dict(action.choices)


def _imports(fn, seen=None):
    """Module names ``fn`` imports when it runs: its import statements, the
    ``ray_tpu.*`` strings it hands to ``__import__``, and the same of the
    functions of ``cli`` it calls."""
    seen = set() if seen is None else seen
    if fn in seen:
        return set()
    seen.add(fn)
    found = set()
    codes = [fn.__code__]
    while codes:
        code = codes.pop()
        codes += [c for c in code.co_consts if isinstance(c, types.CodeType)]
        module = None
        for ins in dis.get_instructions(code):
            if ins.opname == "IMPORT_NAME":
                module = ins.argval
                found.add(module)
            elif ins.opname == "IMPORT_FROM" and module:
                found.add(f"{module}.{ins.argval}")
            elif ins.opname == "LOAD_CONST" and isinstance(ins.argval, str) \
                    and _MODULE.match(ins.argval):
                found.add(ins.argval)
            elif ins.opname == "LOAD_GLOBAL":
                callee = getattr(cli, ins.argval, None)
                if isinstance(callee, types.FunctionType) \
                        and callee.__module__ == cli.__name__:
                    found |= _imports(callee, seen)
    return {m for m in found if m.split(".")[0] == "ray_tpu"}


def _import(name):
    """``name`` is a module, or an attribute of one (``from m import f``)."""
    try:
        importlib.import_module(name)
    except ModuleNotFoundError as e:
        parent, _, attr = name.rpartition(".")
        if e.name != name or not parent:
            raise
        assert hasattr(importlib.import_module(parent), attr), \
            f"{parent} has no {attr}"


def test_the_help_lists_what_this_file_tests(parsers):
    assert sorted(parsers) == sorted(SUBCOMMANDS)
    assert len(set(SUBCOMMANDS)) == len(SUBCOMMANDS)


@pytest.mark.parametrize("name", SUBCOMMANDS)
def test_subcommand_dispatches_to_a_module_that_exists(parsers, name, capsys):
    fn = parsers[name].get_default("fn")
    if fn is None:
        # a stub for the help: main() hands the arguments to the module
        # that owns the flag set before it parses anything
        with pytest.raises(SystemExit) as done:
            cli.main([name, "--help"])
        assert done.value.code == 0
        assert name in capsys.readouterr().out
        return
    modules = _imports(fn)
    for module in sorted(modules):
        _import(module)
    if fn.__name__ == "<lambda>":
        # a lambda is only its dispatch: it must name the module it runs
        assert modules, f"rt {name} dispatches to no module"
