"""pilosa-tpu CLI (ref: cmd/root.go:43-58 subcommand registry).

Usage: python -m pilosa_tpu.cli <command> [flags]
Commands: server, import, export, backup, restore, check, inspect,
bench, generate-config, config.
"""
import sys

from pilosa_tpu.cli import commands


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    cmd, args = argv[0], argv[1:]
    fn = {
        "server": commands.cmd_server,
        "import": commands.cmd_import,
        "export": commands.cmd_export,
        "backup": commands.cmd_backup,
        "restore": commands.cmd_restore,
        "check": commands.cmd_check,
        "inspect": commands.cmd_inspect,
        "bench": commands.cmd_bench,
        "generate-config": commands.cmd_generate_config,
        "config": commands.cmd_config,
    }.get(cmd)
    if fn is None:
        print(f"unknown command: {cmd}", file=sys.stderr)
        print(__doc__)
        return 1
    return fn(args) or 0


if __name__ == "__main__":
    sys.exit(main())
