"""The benchmark's own machinery: cell specs, weights, the
loop that drives a window, the trace reduction, operation counts and the
output check.  Nothing here is imported by the program under test."""
