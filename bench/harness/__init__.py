"""The benchmark's harness: cells found by name, loops, checks, trace
reduction.  Nothing here is imported by the program under test."""
