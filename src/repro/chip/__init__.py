"""The unified chip API: compile → program → stream as one object.

  chip = compile_chip(spec, params=..., system="memristor",
                      items_per_second=...)
  y = chip.stream(x)          # the mapped dataflow, programmed once
  r = chip.report()           # Tables II–VI accounting in one record

See :mod:`repro.chip.compile` for the full design notes.
Self-check:  PYTHONPATH=src python -m repro.chip --selftest
"""
from repro.chip.compile import (ChipRateWarning, CompiledChip,
                                StreamLayer, compile_app, compile_chip,
                                compile_count, program_plan,
                                reprogram_chip, stream_pipeline,
                                validate_stream_rate)
from repro.chip.report import ChipReport, chip_report

__all__ = ["ChipRateWarning", "CompiledChip", "StreamLayer",
           "compile_app", "compile_chip", "compile_count",
           "program_plan", "reprogram_chip", "stream_pipeline",
           "validate_stream_rate",
           "ChipReport", "chip_report"]
