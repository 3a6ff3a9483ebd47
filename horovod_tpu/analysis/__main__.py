"""``python -m horovod_tpu.analysis`` — the hvd-analyze CLI.

Usage:

    python -m horovod_tpu.analysis PATH [PATH ...]     # AST trap lint
    python -m horovod_tpu.analysis --self-lint         # lint this repo
    python -m horovod_tpu.analysis --step MOD:ATTR     # jaxpr analysis
    python -m horovod_tpu.analysis --preflight SCRIPT  # launcher hook
    python -m horovod_tpu.analysis --contracts         # contract matrix

``--step`` imports ``MOD`` (a module name or a ``.py`` path) and calls
the zero-argument factory ``ATTR``, which must return either
``(fn, args_tuple)`` or ``{"fn": fn, "args": (...), "mesh": mesh}``;
the step is then traced abstractly (jaxpr only — nothing runs on a
device) and checked.  ``--preflight`` is what ``runner/launch.py`` runs
under ``HOROVOD_PREFLIGHT_ANALYZE=1``: it lints the entry script and, if
the script defines an ``HVD_ANALYZE`` factory, imports it (module-level
code runs, the ``__main__`` guard does not) and jaxpr-checks the step.

``--contracts`` runs the compiled-program contract registry
(``analysis/contracts.py``): every registered family's programs are
traced/compiled on the 8-device CPU mesh and their HLO summaries checked
against the family's declared invariants; ``--family NAME`` (repeatable)
restricts the matrix.  Needs the tier-1 incantation
(``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Output is one ``file:line: SEVERITY [check-id] message`` line per
finding (``--json`` for JSON lines, ``--sarif`` for one SARIF 2.1.0
document — SARIF wins when both are given).  Exit status: 0 clean or
warnings-only, 1 if any ERROR finding, 2 on usage errors (``--strict``
promotes warnings to the failing exit).
"""

import argparse
import importlib
import json
import os
import sys

from .findings import Finding, Severity, format_findings
from .jaxpr import analyze_step
from .trap_lint import lint_paths

REPO_SELF_LINT_TARGETS = (
    "horovod_tpu", "tests", "benchmarks", "examples", "__graft_entry__.py",
)

ANALYZE_HOOK = "HVD_ANALYZE"


def _repo_root() -> str:
    # horovod_tpu/analysis/__main__.py -> repo root is two dirs up from
    # the package directory.
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.dirname(pkg)


def _load_step_factory(spec: str):
    if ":" not in spec:
        raise SystemExit(f"--step expects MOD:ATTR, got {spec!r}")
    mod_name, attr = spec.rsplit(":", 1)
    if mod_name.endswith(".py"):
        import importlib.util
        spec_obj = importlib.util.spec_from_file_location(
            "hvd_analyze_target", mod_name)
        module = importlib.util.module_from_spec(spec_obj)
        spec_obj.loader.exec_module(module)
    else:
        module = importlib.import_module(mod_name)
    try:
        return getattr(module, attr)
    except AttributeError:
        raise SystemExit(f"{mod_name} has no attribute {attr!r}")


def _run_step_factory(factory):
    spec = factory()
    if isinstance(spec, dict):
        fn = spec["fn"]
        args = tuple(spec.get("args", ()))
        mesh = spec.get("mesh")
    else:
        fn, args = spec[0], tuple(spec[1])
        mesh = None
    return analyze_step(fn, *args, mesh=mesh)


def _script_defines_hook(path: str) -> bool:
    import ast
    try:
        with open(path, "r", encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=path)
    except (OSError, SyntaxError):
        return False
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == ANALYZE_HOOK:
            return True
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == ANALYZE_HOOK:
                    return True
    return False


def _preflight(script: str):
    findings = lint_paths([script])
    if _script_defines_hook(script):
        import importlib.util
        spec_obj = importlib.util.spec_from_file_location(
            "hvd_analyze_preflight", script)
        module = importlib.util.module_from_spec(spec_obj)
        spec_obj.loader.exec_module(module)
        factory = getattr(module, ANALYZE_HOOK, None)
        if callable(factory):
            findings.extend(_run_step_factory(factory))
    return findings


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m horovod_tpu.analysis",
        description="hvd-analyze: static collective-consistency checker "
                    "+ trap lint (see docs/analysis.md)")
    parser.add_argument("paths", nargs="*",
                        help="files/directories to trap-lint")
    parser.add_argument("--self-lint", action="store_true",
                        help="lint this repository's own sources")
    parser.add_argument("--step", metavar="MOD:ATTR",
                        help="jaxpr-analyze the step returned by the "
                             "zero-arg factory ATTR in MOD")
    parser.add_argument("--preflight", metavar="SCRIPT",
                        help="launcher preflight: lint SCRIPT and jaxpr-"
                             f"check its {ANALYZE_HOOK} hook if defined")
    parser.add_argument("--contracts", action="store_true",
                        help="run the compiled-program contract registry "
                             "(analysis/contracts.py)")
    parser.add_argument("--family", action="append", metavar="NAME",
                        help="restrict --contracts to this family "
                             "(repeatable)")
    parser.add_argument("--json", action="store_true",
                        help="emit findings as JSON lines")
    parser.add_argument("--sarif", action="store_true",
                        help="emit findings as one SARIF 2.1.0 document "
                             "(takes precedence over --json)")
    parser.add_argument("--strict", action="store_true",
                        help="exit nonzero on warnings too")
    args = parser.parse_args(argv)

    findings = []
    did_something = False
    if args.self_lint:
        root = _repo_root()
        targets = [os.path.join(root, t) for t in REPO_SELF_LINT_TARGETS]
        findings.extend(lint_paths([t for t in targets
                                    if os.path.exists(t)]))
        did_something = True
    if args.paths:
        findings.extend(lint_paths(args.paths))
        did_something = True
    if args.step:
        findings.extend(_run_step_factory(_load_step_factory(args.step)))
        did_something = True
    if args.preflight:
        findings.extend(_preflight(args.preflight))
        did_something = True
    if args.contracts:
        from . import contracts
        only = args.family or None
        if only:
            unknown = [n for n in only
                       if n not in contracts.families()]
            if unknown:
                print(f"unknown contract families: {unknown}; "
                      f"registered: {contracts.families()}",
                      file=sys.stderr)
                return 2
        findings.extend(contracts.run_contracts(only))
        did_something = True
    elif args.family:
        print("--family requires --contracts", file=sys.stderr)
        return 2

    if not did_something:
        parser.print_usage(sys.stderr)
        return 2

    if args.sarif:
        from .findings import to_sarif
        print(json.dumps(to_sarif(findings)))
    elif args.json:
        for f in findings:
            print(json.dumps(f.to_dict()))
    elif findings:
        print(format_findings(findings))

    if any(f.severity == Severity.ERROR for f in findings):
        return 1
    if args.strict and any(f.severity == Severity.WARNING
                           for f in findings):
        return 1
    if not args.sarif and not args.json and not findings:
        print("hvd-analyze: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
