"""The system under test, built from a configuration file.

This is the one place the benchmark imports the program: the parser
configuration (its DFA found by name as ``repro.core.dfa.make_<dfa>_dfa``),
the streaming session the bulk window drives, and the resolved plan,
printed so that a change of path shows in the log.
"""
from __future__ import annotations

import jax

from repro.core import Parser, ParserConfig, Schema
from repro.core import dfa as dfa_mod
from repro.core.streaming import StreamOverflow, StreamSession  # noqa: F401


def schema_of(config: dict):
    return tuple((name, dtype) for name, dtype in config["schema"])


def make_dfa(parser: dict):
    """``repro.core.dfa.make_<dfa>_dfa(**dfa_args)``."""
    return getattr(dfa_mod, f"make_{parser['dfa']}_dfa")(**parser.get("dfa_args", {}))


def max_records(config: dict, partition_bytes: int) -> int:
    """Records one partition can hold: its bytes plus the carry over the
    shortest record, rounded up to a power of two."""
    cap = partition_bytes + config["parser"]["max_carry_bytes"]
    need = cap // config["min_record_bytes"] + 1
    return 1 << max(4, (need - 1).bit_length())


def parser_config(config: dict, partition_bytes: int) -> ParserConfig:
    p = config["parser"]
    return ParserConfig(
        dfa=make_dfa(p), schema=Schema.of(*schema_of(config)),
        max_records=max_records(config, partition_bytes),
        backend=p["backend"], validate_columns=p["validate_columns"])


def session(config: dict, partition_bytes: int) -> StreamSession:
    return StreamSession(Parser(parser_config(config, partition_bytes)),
                         partition_bytes,
                         max_carry_bytes=config["parser"]["max_carry_bytes"])


def plan_facts(parser: Parser) -> dict:
    plan = parser.plan
    return dict(execute_path=plan.execute_path,
                partition_impl=plan.materialize.partition_impl,
                typeconv_path=plan.materialize.typeconv_path,
                interpret=plan.interpret)


class CompileClock:
    """Seconds and events of JAX compilation (or persistent-cache fetch),
    from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.events += 1


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))
