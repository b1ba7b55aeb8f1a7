"""Operations and bytes the benchmark's programs need, counted from shapes
(never from the compiler's cost analysis, which counts a loop body once)."""
