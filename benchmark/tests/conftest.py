import os
import sys

# the benchmark's own modules import as ``harness.*``, as under run.py
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
