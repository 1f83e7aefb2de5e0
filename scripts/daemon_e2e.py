import sys, time, tempfile
sys.path.insert(0, "src")
from repro.core import Dict
from repro.engine.daemon import Daemon
from repro.provenance.store import configure_store
from repro.calcjobs import TPUTrainJob


def main():
    workdir = tempfile.mkdtemp(prefix="daemon_test_")
    # one worker process: the jobs hold the device, one process per chip
    daemon = Daemon(workdir, workers=1, slots=10)
    daemon.start()
    print("daemon started on", daemon.host, daemon.port)

    pks = []
    for i in range(4):
        pk = daemon.submit(TPUTrainJob, {"config": Dict({
            "arch": "qwen2-0.5b", "steps": 2, "batch": 1, "seq": 16,
            "seed": i})})
        pks.append(pk)
    print("submitted", pks)

    store = configure_store(daemon.store_path)
    t0 = time.time()
    states = {}
    while time.time() - t0 < 150:
        states = {pk: (store.get_node(pk) or {}).get("process_state")
                  for pk in pks}
        if all(s in ("finished", "excepted", "killed")
               for s in states.values()):
            break
        daemon.supervise()
        time.sleep(0.5)
    print("final states:", states)
    for pk in pks:
        n = store.get_node(pk)
        print(pk, n["process_state"], "exit:", n["exit_status"])
    daemon.stop()
    ok = all((store.get_node(pk) or {}).get("exit_status") == 0 for pk in pks)
    print("DAEMON E2E", "PASS" if ok else "FAIL")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
