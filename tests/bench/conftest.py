import os
import sys

# the benchmark package lives at the checkout's root (bench/)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
