"""Every step entry point of models/hgcn.py is one ``cli.train`` runs: a
step builder that no CLI path reaches is a second system that every PR
on the decoder or the aggregation has to keep in step, and that no
benchmark cell can measure."""

import ast
import os
import re

import pytest

_PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "hyperspace_tpu")


def _parse(rel):
    with open(os.path.join(_PKG, rel), encoding="utf-8") as f:
        return ast.parse(f.read())


def _step_builders():
    """The public top-level functions of models/hgcn.py named
    ``train_step_*`` or ``make_*_step_*``."""
    return sorted(
        node.name for node in _parse("models/hgcn.py").body
        if isinstance(node, ast.FunctionDef)
        and re.fullmatch(r"train_step_\w+|make_\w*_step_\w+", node.name))


@pytest.mark.parametrize("name", _step_builders())
def test_every_step_builder_is_reached_from_the_cli(name):
    cli = _parse("cli/train.py")
    used = {n.attr for n in ast.walk(cli) if isinstance(n, ast.Attribute)}
    used |= {n.id for n in ast.walk(cli) if isinstance(n, ast.Name)}
    assert name in used, (
        f"models/hgcn.py defines {name}, which cli/train.py never names")


def test_the_four_step_builders_are_found():
    """The collection above reads the source, not a list: hold it to the
    four (task, placement) steps, so a rename cannot empty it."""
    assert _step_builders() == [
        "make_node_sharded_step_lp", "make_node_sharded_step_nc",
        "train_step_lp", "train_step_nc"]
