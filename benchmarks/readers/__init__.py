"""Per-layer metric readers. A metric file names one as
"<module>.<function>"; each takes the run's observations, in which the
harness names the cell under "cell", and the metric file's `args`, and
returns a number, or None when there is nothing to read (the harness
then leaves the metric out of the line). No metric file names a cell:
an entry of the manifest is one reading, and lists in `workloads` every
cell that reports it. None names a model either: what needs a model's
own arithmetic is read by readers/model.py through the configuration's
helper (benchmarks/yardsticks.py)."""
