import sys, time, tempfile
sys.path.insert(0, "src")
from repro.core import Dict
from repro.engine.daemon import Daemon
from repro.provenance.store import configure_store
from repro.calcjobs import TPUTrainJob


def main():
    workdir = tempfile.mkdtemp(prefix="daemon_crash_")
    # the worker hard-exits (os._exit(17)) ~1.5s after starting — mid-job;
    # one worker process: the jobs hold the device, one process per chip
    daemon = Daemon(workdir, workers=1, slots=10, crash_after=1.5)
    daemon.start()

    pks = [daemon.submit(TPUTrainJob, {"config": Dict({
        "arch": "qwen2-0.5b", "steps": 2, "batch": 1, "seq": 16,
        "seed": i})}) for i in range(4)]
    print("submitted", pks)

    store = configure_store(daemon.store_path)
    t0 = time.time()
    restarts = 0
    states = {}
    while time.time() - t0 < 200:
        states = {pk: (store.get_node(pk) or {}).get("process_state")
                  for pk in pks}
        if all(s in ("finished", "excepted", "killed")
               for s in states.values()):
            break
        r = daemon.supervise()
        if r:
            restarts += r
            # after a few crashes let replacements live
            if restarts >= 4:
                daemon.crash_after = None
        time.sleep(0.4)
    print("restarts:", restarts, "states:", states)
    daemon.stop()
    ok = all((store.get_node(pk) or {}).get("exit_status") == 0
             for pk in pks) and restarts > 0
    print("CRASH RECOVERY", "PASS" if ok else "FAIL")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
