"""Per-layer metric readers, one file per metric, found by name.

Each defines `read(cell, trace, measured)`: the metric's value from the
reduced device trace (`devtrace.Trace`), the driver's counters of the
traced window (`measured`) and the cell's shapes, or None where there
is nothing to read (the harness then leaves the metric out)."""
