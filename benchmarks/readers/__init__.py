"""Per-layer metric readers. A metric file names one as
"<module>.<function>"; each takes the run's observations and the metric
file's `args`, and returns a number, or None when there is nothing to
read (the harness then leaves the metric out of the line)."""
