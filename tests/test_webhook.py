"""Admission webhooks over real HTTP: mutate (JSONPatch), validate
(deny), failurePolicy.

Reference shape: apiserver/pkg/admission/plugin/webhook tests with a live
test server.
"""

import base64
import json
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.apiserver.server import APIServer, Invalid
from kubernetes_tpu.apiserver.webhook import (
    MutatingWebhookConfiguration,
    RuleWithOperations,
    ValidatingWebhookConfiguration,
    Webhook,
    WebhookAdmission,
    WebhookClientConfig,
    apply_json_patch,
)
from kubernetes_tpu.client.clientset import Clientset

from .util import make_pod


class _Handler(BaseHTTPRequestHandler):
    behavior = staticmethod(lambda review: {"allowed": True})
    seen = []

    def do_POST(self):
        length = int(self.headers["Content-Length"])
        review = json.loads(self.rfile.read(length))
        type(self).seen.append(review)
        response = type(self).behavior(review)
        body = json.dumps({
            "apiVersion": "admission.k8s.io/v1",
            "kind": "AdmissionReview",
            "response": {"uid": review["request"]["uid"], **response},
        }).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # quiet
        pass


@pytest.fixture()
def webhook_server():
    _Handler.seen = []
    _Handler.behavior = staticmethod(lambda review: {"allowed": True})
    server = HTTPServer(("127.0.0.1", 0), _Handler)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{server.server_port}/", _Handler
    server.shutdown()


@pytest.fixture()
def cluster():
    api = APIServer()
    WebhookAdmission(api).install()
    return api, Clientset(api)


class TestJSONPatch:
    def test_ops(self):
        doc = {"spec": {"containers": [{"name": "c"}]}, "metadata": {}}
        out = apply_json_patch(doc, [
            {"op": "add", "path": "/metadata/labels", "value": {"a": "b"}},
            {"op": "replace", "path": "/spec/containers/0/name", "value": "x"},
            {"op": "add", "path": "/spec/containers/-", "value": {"name": "y"}},
            {"op": "remove", "path": "/metadata/labels"},
        ])
        assert out["spec"]["containers"][0]["name"] == "x"
        assert out["spec"]["containers"][1]["name"] == "y"
        assert "labels" not in out["metadata"]


class TestValidatingWebhook:
    def test_deny_and_allow(self, cluster, webhook_server):
        api, cs = cluster
        url, handler = webhook_server
        cs.resource("validatingwebhookconfigurations").create(
            ValidatingWebhookConfiguration(
                metadata=v1.ObjectMeta(name="deny-big"),
                webhooks=[Webhook(
                    name="deny.example.com",
                    client_config=WebhookClientConfig(url=url),
                    rules=[RuleWithOperations(operations=["CREATE"], resources=["pods"])],
                )],
            )
        )
        handler.behavior = staticmethod(lambda review: {
            "allowed": review["request"]["object"]["metadata"]["name"] != "bad",
            "status": {"message": "bad pods not allowed"},
        })
        cs.pods.create(make_pod("ok"))
        with pytest.raises(Invalid, match="bad pods not allowed"):
            cs.pods.create(make_pod("bad"))
        # rule scoping: nodes are not covered
        from .util import make_node

        cs.nodes.create(make_node("n1"))
        kinds = [r["request"]["resource"]["resource"] for r in handler.seen]
        assert "nodes" not in kinds

    def test_failure_policy(self, cluster):
        api, cs = cluster
        dead = "http://127.0.0.1:1/"  # nothing listens
        cs.resource("validatingwebhookconfigurations").create(
            ValidatingWebhookConfiguration(
                metadata=v1.ObjectMeta(name="flaky"),
                webhooks=[Webhook(
                    name="fail.example.com",
                    client_config=WebhookClientConfig(url=dead),
                    rules=[RuleWithOperations(operations=["CREATE"], resources=["pods"])],
                    failure_policy="Fail",
                    timeout_seconds=1,
                )],
            )
        )
        with pytest.raises(Invalid, match="failed calling webhook"):
            cs.pods.create(make_pod("p"))
        cfg = cs.resource("validatingwebhookconfigurations").get("flaky")
        cfg.webhooks[0].failure_policy = "Ignore"
        cs.resource("validatingwebhookconfigurations").update(cfg)
        cs.pods.create(make_pod("p"))  # unreachable hook now ignored


class TestWebhookOnTheBulkRoute:
    def test_a_backend_that_reads_this_server_is_answered(
            self, cluster, webhook_server):
        """The event firehose's bulk create with a validating webhook on
        `events` whose backend reads and writes this very API server
        before it answers: the hook runs with no lock of the server's or
        the store's held, so the backend is served, every item is
        admitted, and none waits for the hook's timeout."""
        from kubernetes_tpu.client.events import Event

        api, cs = cluster
        url, handler = webhook_server
        cs.resource("validatingwebhookconfigurations").create(
            ValidatingWebhookConfiguration(
                metadata=v1.ObjectMeta(name="events-hook"),
                webhooks=[Webhook(
                    name="events.example.com",
                    client_config=WebhookClientConfig(url=url),
                    rules=[RuleWithOperations(operations=["CREATE"],
                                              resources=["events"])],
                    failure_policy="Fail",
                    timeout_seconds=3,
                )],
            )
        )
        served = []

        def behavior(review):
            name = review["request"]["object"]["metadata"]["name"]
            api.list("events", "default")
            api.create("configmaps", v1.ConfigMap(metadata=v1.ObjectMeta(
                name=f"saw-{name}", namespace="default")))
            served.append(name)
            return {"allowed": name != "ev-2",
                    "status": {"message": "not ev-2"}}

        handler.behavior = staticmethod(behavior)
        events = [Event(metadata=v1.ObjectMeta(name=f"ev-{i}",
                                               namespace="default"),
                        reason="Scheduled", message=f"m{i}")
                  for i in range(5)]
        assert api.create_bulk("events", events) == 4
        assert served == [f"ev-{i}" for i in range(5)]
        assert sorted(e.metadata.name for e in
                      api.list("events", "default")[0]) == [
            "ev-0", "ev-1", "ev-3", "ev-4"]
        assert len(api.list("configmaps", "default")[0]) == 5


class TestMutatingWebhook:
    def test_jsonpatch_applied(self, cluster, webhook_server):
        api, cs = cluster
        url, handler = webhook_server
        cs.resource("mutatingwebhookconfigurations").create(
            MutatingWebhookConfiguration(
                metadata=v1.ObjectMeta(name="inject"),
                webhooks=[Webhook(
                    name="inject.example.com",
                    client_config=WebhookClientConfig(url=url),
                    rules=[RuleWithOperations(operations=["CREATE"], resources=["pods"])],
                )],
            )
        )
        patch = base64.b64encode(json.dumps([
            {"op": "add", "path": "/metadata/labels", "value": {"injected": "yes"}},
            {"op": "add", "path": "/spec/priority", "value": 7},
        ]).encode()).decode()
        handler.behavior = staticmethod(lambda review: {
            "allowed": True, "patchType": "JSONPatch", "patch": patch,
        })
        created = cs.pods.create(make_pod("p"))
        assert created.metadata.labels["injected"] == "yes"
        assert created.spec.priority == 7
        # the stored object carries the mutation too
        assert cs.pods.get("p", "default").spec.priority == 7


class TestWebhookFixes:
    def test_patched_object_keeps_server_stamps(self, cluster, webhook_server):
        api, cs = cluster
        url, handler = webhook_server
        cs.resource("mutatingwebhookconfigurations").create(
            MutatingWebhookConfiguration(
                metadata=v1.ObjectMeta(name="inject"),
                webhooks=[Webhook(
                    name="inject.example.com",
                    client_config=WebhookClientConfig(url=url),
                    rules=[RuleWithOperations(operations=["CREATE"], resources=["pods"])],
                )],
            )
        )
        patch = base64.b64encode(json.dumps([
            {"op": "add", "path": "/metadata/labels", "value": {"x": "y"}},
        ]).encode()).decode()
        handler.behavior = staticmethod(lambda review: {
            "allowed": True, "patchType": "JSONPatch", "patch": patch,
        })
        created = cs.pods.create(make_pod("p"))
        # server stamps must survive the in-place patch (uid/creation time
        # are stamped via the metadata alias held by create())
        assert created.metadata.uid
        assert created.metadata.creation_timestamp is not None
        assert created.metadata.labels["x"] == "y"

    def test_delete_webhook_fires(self, cluster, webhook_server):
        api, cs = cluster
        url, handler = webhook_server
        cs.pods.create(make_pod("keep"))
        cs.resource("validatingwebhookconfigurations").create(
            ValidatingWebhookConfiguration(
                metadata=v1.ObjectMeta(name="guard"),
                webhooks=[Webhook(
                    name="guard.example.com",
                    client_config=WebhookClientConfig(url=url),
                    rules=[RuleWithOperations(operations=["DELETE"], resources=["pods"])],
                )],
            )
        )
        handler.behavior = staticmethod(lambda review: {
            "allowed": review["request"]["operation"] != "DELETE",
            "status": {"message": "deletion guarded"},
        })
        with pytest.raises(Invalid, match="deletion guarded"):
            cs.pods.delete("keep", "default")
        assert cs.pods.get("keep", "default")
        handler.behavior = staticmethod(lambda review: {"allowed": True})
        cs.pods.delete("keep", "default")

    def test_malformed_response_honors_failure_policy(self, cluster, webhook_server):
        api, cs = cluster
        url, handler = webhook_server

        class Raw:
            pass

        # respond 200 with a body that has no "response" object
        def weird(review):
            return {}  # merged under "response" by the handler... bypass:
        # patch the handler to send a body without "response"
        import json as _json

        def do_POST(self):
            length = int(self.headers["Content-Length"])
            self.rfile.read(length)
            body = _json.dumps({"kind": "AdmissionReview"}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        orig = handler.do_POST
        handler.do_POST = do_POST
        try:
            cs.resource("validatingwebhookconfigurations").create(
                ValidatingWebhookConfiguration(
                    metadata=v1.ObjectMeta(name="weird"),
                    webhooks=[Webhook(
                        name="weird.example.com",
                        client_config=WebhookClientConfig(url=url),
                        rules=[RuleWithOperations(operations=["CREATE"], resources=["pods"])],
                        failure_policy="Ignore",
                    )],
                )
            )
            cs.pods.create(make_pod("ok-despite-weird"))  # Ignore -> allowed
            cfg = cs.resource("validatingwebhookconfigurations").get("weird")
            cfg.webhooks[0].failure_policy = "Fail"
            cs.resource("validatingwebhookconfigurations").update(cfg)
            with pytest.raises(Invalid, match="failed calling webhook"):
                cs.pods.create(make_pod("rejected"))
        finally:
            handler.do_POST = orig
