import time

T0 = time.perf_counter()  # set-up is counted from here: before any import

import sys  # noqa: E402

from perfbench.harness import main  # noqa: E402

sys.exit(main(sys.argv[1:], T0))
