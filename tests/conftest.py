"""Test-process setup, applied before any test module imports jax.

XLA's CPU backend contracts ``a * b + c`` into one fused multiply-add
wherever its fusion decisions allow, and those decisions differ between the
oracle, the engine and the interpret-mode kernels (and between jax
releases): a 1-ulp difference that belongs to the compiler, not to the code
under test.  Capping the CPU ISA below FMA makes every path round each
multiply and each add, so the suite's bit-equality checks compare the
stencil arithmetic itself.  TPU compiles are unaffected.
"""
import os

_NO_FMA = "--xla_cpu_max_isa=AVX"
if _NO_FMA not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _NO_FMA).strip()
