"""The CLI's surface: which subcommands exist, and that each runs.

A subparser registered without a handler is an error nobody sees until
an operator types the command, and a deleted subcommand that argparse
still accepts is a promise the program no longer keeps. Device-free.
"""

import pytest

from akka_allreduce_tpu import cli

SUBCOMMANDS = ("emulate", "master", "worker", "train", "generate",
               "serve", "eval", "lint", "replica-worker", "info")
# deleted in PR 29 with the measuring apparatus they drove
REMOVED = ("bench", "perfgate", "stress")


def test_the_table_is_the_surface():
    """What argparse accepts is what has a handler, and is this list."""
    (sub,) = [a for a in cli._build_parser()._actions if a.dest == "cmd"]
    assert set(sub.choices) == set(cli._COMMANDS) == set(SUBCOMMANDS)


@pytest.mark.parametrize("cmd", SUBCOMMANDS)
def test_subcommand_has_help_and_a_handler(cmd, capsys):
    with pytest.raises(SystemExit) as exc:
        cli.main([cmd, "--help"])
    assert exc.value.code == 0
    assert cmd in capsys.readouterr().out
    assert callable(cli._COMMANDS[cmd][1])


@pytest.mark.parametrize("cmd", REMOVED)
def test_removed_subcommand_is_rejected(cmd, capsys):
    with pytest.raises(SystemExit) as exc:
        cli.main([cmd])
    assert exc.value.code == 2
    assert "invalid choice" in capsys.readouterr().err
